"""AlexNet3D_Dropout, inference forward pass, plain float32.

Written from the published description (reference
``fedml_api/model/cv/salient_models.py:142-191``):

    features: Conv3d(1, 64, k5, s2, p0)  BN ReLU MaxPool3d(3, 3)
              Conv3d(64, 128, k3, s1, p0) BN ReLU MaxPool3d(3, 3)
              Conv3d(128, 192, k3, p1) BN ReLU
              Conv3d(192, 192, k3, p1) BN ReLU
              Conv3d(192, 128, k3, p1) BN ReLU MaxPool3d(3, 3)
    classifier: Dropout Linear(flat, 64) ReLU Dropout Linear(64, classes)

Departures, all forced by taking the weights of the system under test:
channels-last layout (the flatten order is therefore D, H, W, C and the
first Linear's rows are in that order), and the parameter tree's names
(``f0..f4/{conv,bn}``, ``fc1``, ``fc2``). Dropout is the identity at
inference; batch norm uses the running statistics.
"""

from benchmark.reference import ops

STAGES = (("f0", 2, 0, True), ("f1", 1, 0, True), ("f2", 1, 1, False),
          ("f3", 1, 1, False), ("f4", 1, 1, True))  # name, stride, pad, pool


def forward(params, batch_stats, x_uint8, tape=None):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``."""
    import jax

    x = ops.prep(x_uint8)
    for name, stride, pad, pool in STAGES:
        p = params[name]
        x = ops.conv3d(x, p["conv"]["kernel"], p["conv"]["bias"],
                       stride=stride, pad=pad, tape=tape,
                       name=f"{name}/conv")
        x = jax.nn.relu(ops.batch_norm_eval(x, p["bn"],
                                            batch_stats[name]["bn"]))
        if pool:
            x = ops.max_pool(x, 3, 3)
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(ops.dense(x, params["fc1"]["kernel"],
                              params["fc1"]["bias"], tape=tape, name="fc1"))
    return ops.dense(x, params["fc2"]["kernel"], params["fc2"]["bias"],
                     tape=tape, name="fc2")
