"""ResNet_l3 with BasicBlock, layers (1, 1, 1): inference forward, float32.

Written from the published description (reference
``fedml_api/model/cv/salient_models.py:13-42, 84-139``):

    conv1: Conv3d(1, 64, k3, s2, p3, no bias) BN ReLU MaxPool3d(k3, s2, p1)
    layer1: BasicBlock(64 -> 64,  stride 1)
    layer2: BasicBlock(64 -> 128, stride 2, downsample 1x1 conv + BN)
    layer3: BasicBlock(128 -> 256, stride 2, downsample 1x1 conv + BN)
    AvgPool3d(3, 3), flatten, Linear(flat, 512), Linear(512, classes)

    BasicBlock: conv3x3(stride) BN ReLU conv3x3 BN, + residual, ReLU

There is no ReLU between the two Linear layers (the reference has none).
Departures forced by taking the weights of the system under test:
channels-last layout and the parameter tree's names (``conv1``, ``bn1``,
``layer{1,2,3}_0/{conv1,bn1,conv2,bn2,ds_conv,ds_bn}``, ``fc``, ``fc2``).
"""

from benchmark.reference import ops


def _block(x, p, s, stride, tape, name):
    import jax

    out = ops.conv3d(x, p["conv1"]["kernel"], stride=stride, pad=1,
                     tape=tape, name=f"{name}/conv1")
    out = jax.nn.relu(ops.batch_norm_eval(out, p["bn1"], s["bn1"]))
    out = ops.conv3d(out, p["conv2"]["kernel"], stride=1, pad=1, tape=tape,
                     name=f"{name}/conv2")
    out = ops.batch_norm_eval(out, p["bn2"], s["bn2"])
    if "ds_conv" in p:
        x = ops.conv3d(x, p["ds_conv"]["kernel"], stride=stride, pad=0,
                       tape=tape, name=f"{name}/ds_conv")
        x = ops.batch_norm_eval(x, p["ds_bn"], s["ds_bn"])
    return jax.nn.relu(out + x)


def forward(params, batch_stats, x_uint8, tape=None):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``."""
    import jax

    x = ops.prep(x_uint8)
    x = ops.conv3d(x, params["conv1"]["kernel"], stride=2, pad=3, tape=tape,
                   name="conv1")
    x = jax.nn.relu(ops.batch_norm_eval(x, params["bn1"],
                                        batch_stats["bn1"]))
    x = ops.max_pool(x, 3, 2, pad=1)
    for stage, stride in ((1, 1), (2, 2), (3, 2)):
        name = f"layer{stage}_0"
        x = _block(x, params[name], batch_stats[name], stride, tape, name)
    x = ops.avg_pool(x, 3, 3)
    x = x.reshape((x.shape[0], -1))
    x = ops.dense(x, params["fc"]["kernel"], params["fc"]["bias"],
                  tape=tape, name="fc")
    return ops.dense(x, params["fc2"]["kernel"], params["fc2"]["bias"],
                     tape=tape, name="fc2")
