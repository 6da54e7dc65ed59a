"""The benchmark's own cohort generator: seeded, cheap, site sizes given.

The program's ``data/synthetic.py::generate_synthetic_abcd`` draws a fresh
Gaussian volume per subject (0.4 s a subject at 121x145x121) and draws the
site sizes from the seed. A cell needs hundreds to thousands of subjects in
every run and fixed site sizes, so this generator

- takes the site sizes from the traffic file: only voxels and labels
  depend on ``seed``;
- draws ``NOISE_VOLUMES`` noise volumes once and gives each subject one of
  them rolled by a seeded offset, plus its class's blob and its site's
  intensity offset, all in uint8 (about 3 ms a subject);
- keeps the class signal of the program's generator (a smooth central blob
  whose amplitude differs by class), so the task stays learnable and
  ``correct`` can hold the run to a loss band and an AUC floor.

The cohort is written once as an HDF5 file in the reference schema (``X``
uint8 ``[N, D, H, W]``, ``y``, ``site``), which is the path a private cohort
takes into the program (``--dataset abcd_h5 --data_dir <file>``).
"""

from __future__ import annotations

import glob
import os
import zlib

import numpy as np

NOISE_VOLUMES = 8
BASE_LEVEL = 60.0      # mean grey level, as in the program's generator
NOISE_SD = 8.0
BLOB_LEVEL = 20.0      # blob amplitude common to both classes
CLASS_SIGNAL = 12.0    # +/- by class, the program generator's default
SITE_OFFSET_MAX = 8    # per-site intensity offset (scanner gain), grey levels
WRITE_BLOCK = 32       # subjects per HDF5 write


def site_labels(site_sizes: list[int]) -> np.ndarray:
    """Site label of every subject: subjects are laid out site by site."""
    return np.repeat(np.arange(len(site_sizes), dtype=np.int16), site_sizes)


def split_counts(site_sizes: list[int], test_frac: float = 0.2
                 ) -> tuple[list[int], list[int]]:
    """(train, test) sizes per site under the program's site partition
    (``data/partition.py::site_partition``: ``n_test = int(n * 0.2)``).
    Kept here so that the count of real samples is the benchmark's own."""
    test = [int(n * test_frac) for n in site_sizes]
    return [n - t for n, t in zip(site_sizes, test)], test


def _blob(shape: tuple[int, int, int]) -> np.ndarray:
    d, h, w = shape
    zz, yy, xx = np.meshgrid(np.linspace(-1, 1, d), np.linspace(-1, 1, h),
                             np.linspace(-1, 1, w), indexing="ij")
    return np.exp(-((zz ** 2 + yy ** 2 + xx ** 2) / 0.18)).ravel()


def generate(site_sizes: list[int], shape: tuple[int, int, int], seed: int):
    """Yield ``(X_block uint8 [b, D, H, W], y_block int8 [b])`` in subject
    order, ``WRITE_BLOCK`` subjects at a time."""
    rng = np.random.default_rng([int(seed), 0xABCD])
    voxels = int(np.prod(shape))
    noise = np.clip(BASE_LEVEL + rng.normal(0.0, NOISE_SD,
                                            (NOISE_VOLUMES, voxels)),
                    0, 127).astype(np.uint8)
    blob = _blob(shape)
    by_class = np.stack([
        np.rint((BLOB_LEVEL - CLASS_SIGNAL) * blob),
        np.rint((BLOB_LEVEL + CLASS_SIGNAL) * blob)]).astype(np.uint8)
    site_offset = rng.integers(0, SITE_OFFSET_MAX + 1, len(site_sizes),
                               dtype=np.uint8)
    site = site_labels(site_sizes)
    n = len(site)
    # balanced labels inside every site, in seeded order
    y = np.concatenate([rng.permutation(np.arange(s) % 2)
                        for s in site_sizes]).astype(np.int8)
    which = rng.integers(0, NOISE_VOLUMES, n)
    shift = rng.integers(0, voxels, n)
    for start in range(0, n, WRITE_BLOCK):
        stop = min(start + WRITE_BLOCK, n)
        block = np.empty((stop - start, voxels), np.uint8)
        for j, i in enumerate(range(start, stop)):
            # 127 + 32 + 8 < 256: the uint8 sum cannot wrap
            np.add(np.roll(noise[which[i]], shift[i]), by_class[y[i]],
                   out=block[j])
            block[j] += site_offset[site[i]]
        yield block.reshape((stop - start,) + tuple(shape)), y[start:stop]


def write_hdf5(path: str, site_sizes: list[int],
               shape: tuple[int, int, int], seed: int) -> None:
    import h5py

    n = int(sum(site_sizes))
    tmp = path + ".tmp"
    with h5py.File(tmp, "w") as f:
        # contiguous, h5py's default layout: through the program's
        # data/hdf5.py::fetch_rows (an h5py fancy read) a dataset chunked
        # by subject reads at 17 MB/s, a contiguous one some twenty times
        # faster (PERF.md section 6, PR 22)
        X = f.create_dataset("X", (n,) + tuple(shape), np.uint8)
        y = f.create_dataset("y", (n,), np.int8)
        f.create_dataset("site", data=site_labels(site_sizes))
        at = 0
        for xb, yb in generate(site_sizes, shape, seed):
            X[at:at + len(yb)] = xb
            y[at:at + len(yb)] = yb
            at += len(yb)
    os.replace(tmp, path)


def ensure_cohort(cache_dir: str, traffic: str, site_sizes: list[int],
                  shape: tuple[int, int, int], seed: int) -> tuple[str, bool]:
    """The cell's cohort file, written if it is not there. Returns
    ``(path, written)``.

    Keyed by traffic name, shape, site table and seed, so a second run of
    the cell with the same seed only reads it. One file per traffic name is
    kept: a run with another seed replaces it, which bounds the directory
    at one cohort per traffic mix (4.3 GB for the largest)."""
    os.makedirs(cache_dir, exist_ok=True)
    key = "x".join(map(str, shape)) + "_" + format(
        zlib.crc32(repr(list(site_sizes)).encode()), "08x")
    path = os.path.join(cache_dir, f"{traffic}__{key}__seed{seed}.h5")
    if os.path.exists(path):
        return path, False
    for old in glob.glob(os.path.join(cache_dir, f"{traffic}__*")):
        os.unlink(old)
    write_hdf5(path, site_sizes, shape, seed)
    return path, True
