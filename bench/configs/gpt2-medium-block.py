"""GPT-2 medium, one block (openai-community/gpt2-medium: n_embd 1024,
n_ctx 1024, n_head 16, d_ff 4096), as the program writes it.

``block`` and ``loss`` are the user's model source in CODO's frontend ops:
a copy of the program's ``gpt2_block_fn``/``gpt2_block_loss_fn``, kept
here so that a rewrite of the program's model zoo does not change what
this configuration measures.  ``reference`` and ``reference_loss`` are the
same block in plain ``jax.numpy``, independent of the program; each
contraction goes through ``ops.mm`` so that the harness sets its
precision (``HIGHEST`` for the reference, lower for the control).
``adamw`` is the update that the configuration states (the JSON's
``optimizer``), written from the published AdamW with global-norm clipping
and a warmup-cosine schedule.

Weights come as a list in the order the block creates them: q, k, v,
proj (each (D, D)), fc1 (D, 4D), fc2 (4D, D).
"""

import math

import jax
import jax.numpy as jnp


def block(x):
    from repro.core import frontend as F
    D = x.shape[1]
    x = F.load(x)
    q = F.fc(x, D)
    k = F.fc(x, D)
    v = F.fc(x, D)
    kt = F.transpose(k)
    s = F.scale(F.matmul(q, kt), 1.0 / math.sqrt(D // 16))
    p = F.softmax(s)
    att = F.matmul(p, v)
    proj = F.fc(att, D)
    h = F.add(proj, x)
    f = F.fc(h, 4 * D)
    f = F.gelu(f)
    f = F.fc(f, D)
    return F.add(f, h)


def loss(x, target):
    from repro.core import frontend as F
    d = F.sub(block(x), target)
    return F.mean_all(F.mul(d, d))


def reference(cfg, ops, weights, x):
    wq, wk, wv, wo, w1, w2 = weights
    mm = ops.mm
    q, k, v = mm(x, wq), mm(x, wk), mm(x, wv)
    scale = 1.0 / math.sqrt(cfg["n_embd"] // cfg["n_head"])
    p = jax.nn.softmax(mm(q, k.T) * scale, axis=-1)
    h = mm(mm(p, v), wo) + x
    return mm(jax.nn.gelu(mm(h, w1), approximate=True), w2) + h


def reference_loss(cfg, ops, weights, x, target):
    d = reference(cfg, ops, weights, x) - target
    return jnp.mean(d * d)


def adamw(cfg, params, grads, m, v, step):
    """One AdamW step from optimizer state after ``step`` steps; returns
    (params, m, v) after step ``step + 1``."""
    oc = cfg["optimizer"]
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads))
    scale = jnp.minimum(1.0, oc["clip_norm"] / jnp.maximum(norm, 1e-9))
    t = step + 1
    if t < oc["warmup_steps"]:
        lr = oc["lr"] * (t + 1) / oc["warmup_steps"]
    else:
        prog = min(max((t - oc["warmup_steps"])
                       / (oc["total_steps"] - oc["warmup_steps"]), 0.0), 1.0)
        lr = oc["lr"] * (oc["min_lr_frac"] + (1 - oc["min_lr_frac"])
                         * 0.5 * (1 + math.cos(math.pi * prog)))
    out_p, out_m, out_v = [], [], []
    for p, g, m1, v1 in zip(params, grads, m, v):
        g = g * scale
        m2 = oc["b1"] * m1 + (1 - oc["b1"]) * g
        v2 = oc["b2"] * v1 + (1 - oc["b2"]) * g * g
        mh = m2 / (1 - oc["b1"] ** t)
        vh = v2 / (1 - oc["b2"] ** t)
        out_p.append(p - lr * (mh / (jnp.sqrt(vh) + oc["eps"])
                               + oc["weight_decay"] * p))
        out_m.append(m2)
        out_v.append(v2)
    return out_p, out_m, out_v
