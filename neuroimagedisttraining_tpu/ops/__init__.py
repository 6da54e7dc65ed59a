"""Kernels and array routines: a leaf. Nothing here imports ``models``,
``core`` or ``engines``; every user imports the submodule it needs."""
