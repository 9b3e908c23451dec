"""ResNet-18 at 224x224, batch 1 (He et al. 2015, microsoft/resnet-18),
as the program writes it.

``block`` is the user's model source in CODO's frontend ops: a copy of the
program's ``resnet18_fn`` at H >= 224, kept here so that a rewrite of the
program's model zoo does not change what this configuration measures.
``reference`` is the same network in plain ``jax.numpy``/``jax.lax``,
independent of the program; each convolution and matmul goes through
``ops`` so that the harness sets its precision.

Weights come as a list in the order the network creates them: the stem
conv, then per basic block conv1, conv2 and (where the block downsamples)
the 1x1 shortcut, then the (512, 1000) classifier.
"""

import jax
import jax.numpy as jnp


def block(x):
    from repro.core import frontend as F
    h = F.conv(x, 64, 7, stride=2, pad=3)
    h = F.maxpool(h, 2)
    for stage, (c, blocks) in enumerate([(64, 2), (128, 2), (256, 2), (512, 2)]):
        for blk in range(blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            inp = h
            h1 = F.conv(inp, c, 3, stride=stride)
            h2 = F.conv(h1, c, 3, relu=False)
            if stride != 1 or inp.shape[1] != c:
                skip = F.conv(inp, c, 1, stride=stride, pad=0, relu=False)
            else:
                skip = inp
            h = F.relu(F.add(h2, skip))
    h = F.global_avgpool(h)
    return F.fc(h, 1000)


def reference(cfg, ops, weights, x):
    ws = iter(weights)

    def conv(h, stride, pad):
        return ops.conv(h, next(ws), stride, pad)

    relu = jax.nn.relu
    h = relu(conv(x, 2, 3))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                              (1, 1, 2, 2), "VALID")
    c_in = cfg["embedding_size"]
    for stage, (c, blocks) in enumerate(zip(cfg["hidden_sizes"],
                                            cfg["depths"])):
        for blk in range(blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            h1 = relu(conv(h, stride, 1))
            h2 = conv(h1, 1, 1)
            skip = conv(h, stride, 0) if stride != 1 or c_in != c else h
            h = relu(h2 + skip)
            c_in = c
    return ops.mm(jnp.mean(h, axis=(2, 3)), next(ws))
