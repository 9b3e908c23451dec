"""granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro, model_type
granitemoehybrid), one whole period of its layer pattern: layers 10-19,
nine Mamba-2 layers and one causal GQA attention layer, each followed by a
SwiGLU MLP, at the published widths (the JSON beside this file).

``block`` is the model source in CODO's frontend ops, sized by the JSON;
``period(cfg, x)`` is the same at any sizes (the tests run it small).
Each layer's mixer and MLP sit in a ``region`` (``mamba``, ``attn``,
``mlp``), which names their ops in the compiled program.  ``reference``
is the same period in plain ``jax.numpy``, independent of the program:
its SSD is the Mamba-2 paper's minimal chunked listing (segment sums,
diagonal blocks, chunk states, the inter-chunk recurrence as a matrix of
chunk decays, state to output) with the listing code's stable segment
sums, the decay to each chunk's end read from them too, and every
contraction through ``ops.mm`` so that the harness sets its precision.
Both sides sum the decays between two positions directly: a difference
of two cumulative sums of ~256 steps (|sum| up to ~2e3) cancels in f32 to
~1e-5 of the output, as much as computing the contractions at ``HIGH``.

Weights come as a list in the order the block creates them.  Per Mamba-2
layer: norm (d,), in_proj (d, 2·di + 2·N + H), conv taps (K, di + 2·N),
conv bias (di + 2·N,), dt_bias (H,), A_log (H,), D (H,), gated-norm scale
(di,), out_proj (di, d); per attention layer: norm (d,), q (d, Hq·hd),
k (d, Hkv·hd), v (d, Hkv·hd), o (Hq·hd, d); after either mixer: norm
(d,), MLP in (d, 2·I), MLP out (I, d).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp


def layer_kinds(cfg) -> list:
    first = cfg["first_layer"]
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


def _sizes(cfg) -> dict:
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": cfg["hidden_size"], "eps": cfg["rms_norm_eps"],
            "rm": cfg["residual_multiplier"], "H": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "L": cfg["mamba_chunk_size"],
            "hq": hq, "hkv": hkv, "hd": cfg["hidden_size"] // hq,
            "am": cfg["attention_multiplier"],
            "I": cfg["shared_intermediate_size"]}


# --------------------------------------------------------------------------
# The block, in CODO's frontend ops
# --------------------------------------------------------------------------


def block(x):
    return period(json.loads(Path(__file__).with_suffix(".json").read_text()),
                  x)


def period(cfg, x):
    from repro.core import frontend as F
    z = _sizes(cfg)
    h = x
    for kind in layer_kinds(cfg):
        mixer = _attention if kind == "attention" else _mamba
        with F.region("attn" if kind == "attention" else "mamba"):
            h = F.add(h, F.scale(mixer(z, F.rmsnorm(h, z["eps"])), z["rm"]))
        with F.region("mlp"):
            h = F.add(h, F.scale(_mlp(z, F.rmsnorm(h, z["eps"])), z["rm"]))
    return h


def _mlp(z, u):
    from repro.core import frontend as F
    gate, up = F.split(F.fc(u, 2 * z["I"]), (z["I"], z["I"]), axis=1)
    return F.fc(F.mul(F.silu(gate), up), z["d"])


def _attention(z, u):
    from repro.core import frontend as F
    s, hq, hkv, hd = u.shape[0], z["hq"], z["hkv"], z["hd"]
    q = F.transpose(F.reshape(F.fc(u, hq * hd), (s, hq, hd)), (1, 0, 2))
    kt = F.transpose(F.reshape(F.fc(u, hkv * hd), (s, hkv, hd)), (1, 2, 0))
    v = F.transpose(F.reshape(F.fc(u, hkv * hd), (s, hkv, hd)), (1, 0, 2))
    g = hq // hkv
    scores = F.scale(F.matmul(q, F.repeat_heads(kt, g)), z["am"])
    o = F.matmul(F.softmax(F.causal_mask(scores)), F.repeat_heads(v, g))
    return F.fc(F.reshape(F.transpose(o, (1, 0, 2)), (s, hq * hd)), z["d"])


def _mamba(z, u):
    from repro.core import frontend as F
    s, H, P, N = u.shape[0], z["H"], z["P"], z["N"]
    di = H * P
    zg, xbc, dt = F.split(F.fc(u, 2 * di + 2 * N + H), (di, di + 2 * N, H),
                          axis=1)
    x, B, C = F.split(F.silu(F.conv1d_causal(xbc, z["K"])), (di, N, N),
                      axis=1)
    dt = F.softplus(F.add(dt, F.param((H,))))             # dt_bias
    A = F.scale(F.exp(F.param((H,))), -1.0)               # A_log
    D = F.param((H,))
    xh = F.reshape(x, (s, H, P))
    y = _ssd(z, F.mul(xh, F.reshape(dt, (s, H, 1))), F.mul(dt, A), B, C)
    y = F.add(y, F.mul(xh, F.reshape(D, (H, 1))))
    y = F.mul(F.reshape(y, (s, di)), F.silu(zg))
    return F.fc(F.rmsnorm(y, z["eps"]), z["d"])


def _ssd(z, X, dA, B, C):
    """Chunked SSD: ``X (S, H, P)`` (x already times dt), ``dA (S, H)``,
    one group's ``B, C (S, N)``; returns ``Y (S, H, P)``.  The segment
    sums add the steps between two positions (a cumulative sum down the
    rows of the steps kept strictly below the diagonal), not a difference
    of two long cumulative sums, which in f32 cancels to ~1e-5 of the
    output."""
    from repro.core import frontend as F
    s, H, P = X.shape
    N, L = B.shape[1], z["L"]
    nc = s // L
    acs = F.cumsum(F.reshape(dA, (nc, L, H)), axis=1)             # (nc,L,H)
    steps = F.reshape(F.transpose(F.reshape(dA, (nc, L, H)), (0, 2, 1)),
                      (nc, H, L, 1))
    seg = F.cumsum(F.causal_mask(F.broadcast_to(steps, (nc, H, L, L)),
                                 diagonal=-1, fill=0.0), axis=2)
    decay = F.exp(F.causal_mask(seg))                              # (nc,H,L,L)
    bt = F.transpose(F.reshape(B, (nc, L, N)), (0, 2, 1))          # (nc,N,L)
    c = F.reshape(C, (nc, L, N))
    m = F.mul(decay, F.reshape(F.matmul(c, bt), (nc, 1, L, L)))
    x4 = F.reshape(X, (nc, L, H, P))
    y_diag = F.transpose(F.matmul(m, F.transpose(x4, (0, 2, 1, 3))),
                         (0, 2, 1, 3))                             # (nc,L,H,P)
    to_end = F.slice_(seg, (0, 0, L - 1, 0), (nc, H, 1, L))        # (nc,H,1,L)
    xd = F.mul(x4, F.transpose(F.exp(to_end), (0, 3, 1, 2)))
    states = F.matmul(bt, F.reshape(xd, (nc, L, H * P)))           # (nc,N,HP)
    states = F.transpose(F.reshape(states, (nc, N, H, P)), (0, 2, 3, 1))
    last = F.slice_(acs, (0, L - 1, 0), (nc, 1, H))                # (nc,1,H)
    prev = F.ssd_scan(states, F.reshape(F.exp(last), (nc, H, 1, 1)))
    prev = F.reshape(F.transpose(prev, (0, 3, 1, 2)), (nc, N, H * P))
    y_off = F.mul(F.reshape(F.matmul(c, prev), (nc, L, H, P)),
                  F.reshape(F.exp(acs), (nc, L, H, 1)))
    return F.reshape(F.add(y_diag, y_off), (s, H, P))


# --------------------------------------------------------------------------
# The plain reference
# --------------------------------------------------------------------------


def reference(cfg, ops, weights, x):
    z = _sizes(cfg)
    w = iter(weights)
    h = x
    for kind in layer_kinds(cfg):
        mixer = _ref_attention if kind == "attention" else _ref_mamba
        h = h + z["rm"] * mixer(z, ops, w, _rms(h, next(w), z["eps"]))
        h = h + z["rm"] * _ref_mlp(z, ops, w, _rms(h, next(w), z["eps"]))
    return h


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _ref_mlp(z, ops, w, u):
    w_in, w_out = next(w), next(w)
    gate, up = jnp.split(ops.mm(u, w_in), 2, axis=-1)
    return ops.mm(jax.nn.silu(gate) * up, w_out)


def _ref_attention(z, ops, w, u):
    wq, wk, wv, wo = next(w), next(w), next(w), next(w)
    s, hq, hkv, hd = u.shape[0], z["hq"], z["hkv"], z["hd"]
    g = hq // hkv
    q = ops.mm(u, wq).reshape(s, hkv, g, hd).transpose(1, 2, 0, 3)
    k = ops.mm(u, wk).reshape(s, hkv, hd).transpose(1, 0, 2)
    v = ops.mm(u, wv).reshape(s, hkv, hd).transpose(1, 0, 2)
    keep = jnp.tril(jnp.ones((s, s), bool))
    heads = []
    for j in range(hkv):            # one K/V head's query group at a time
        sc = ops.mm(q[j], k[j].T) * z["am"]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        heads.append(ops.mm(p, v[j]))                         # (g, s, hd)
    o = jnp.concatenate(heads, axis=0)                        # (hq, s, hd)
    return ops.mm(o.transpose(1, 0, 2).reshape(s, hq * hd), wo)


def _ref_mamba(z, ops, w, u):
    w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, w_out = (
        next(w) for _ in range(8))
    s, H, P, N, K = u.shape[0], z["H"], z["P"], z["N"], z["K"]
    di = H * P
    zxbcdt = ops.mm(u, w_in)
    zg, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N],
                   zxbcdt[:, 2 * di + 2 * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = conv_b + sum(conv_w[i] * padded[i:i + s] for i in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(s, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + dt_bias)
    A = -jnp.exp(a_log)
    y = ssd_minimal(ops, x * dt[:, :, None], dt * A, B, C, z["L"])
    y = (y + x * d_skip[:, None]).reshape(s, di)
    return ops.mm(_rms(y * jax.nn.silu(zg), norm_w, z["eps"]), w_out)


def segsum(x):
    """``out[..., i, j] = Σ_{k=j+1..i} x[..., k]`` for ``j <= i``, else −inf:
    the listing's stable segment sum, a cumulative sum down the rows of
    ``x`` kept strictly below the diagonal."""
    t = x.shape[-1]
    below = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x[..., :, None],
                      0.0)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                     jnp.cumsum(below, axis=-2), -jnp.inf)


def ssd_minimal(ops, X, A, B, C, block_len):
    """The Mamba-2 paper's minimal chunked SSD with one group and no batch:
    ``X (S, H, P)``, ``A (S, H)`` (dt·A), ``B, C (S, N)``; returns
    ``Y (S, H, P)`` with ``h_t = exp(A_t) h_{t-1} + X_t ⊗ B_t``,
    ``Y_t = C_t · h_t``, from a zero state."""
    s, H, P = X.shape
    N, nc, l = B.shape[1], s // block_len, block_len
    X = X.reshape(nc, l, H, P)
    B, C = B.reshape(nc, l, N), C.reshape(nc, l, N)
    A = A.reshape(nc, l, H).transpose(2, 0, 1)                  # (H, nc, l)
    a_cs = jnp.cumsum(A, axis=-1)
    # 1. outputs of the diagonal blocks
    cb = ops.mm(C, B.transpose(0, 2, 1))                        # (nc, l, l)
    seg = segsum(A)
    m = jnp.exp(seg) * cb[None]                                 # (H, nc, l, l)
    y_diag = ops.mm(m, X.transpose(2, 0, 1, 3))                 # (H, nc, l, P)
    # 2. each chunk's final state from its own inputs (the decay to the
    # chunk's end: the segment sums' last row, not a_cs[-1] - a_cs, which
    # cancels in f32)
    decay_states = jnp.exp(seg[:, :, -1, :])                    # (H, nc, l)
    xd = X * decay_states.transpose(1, 2, 0)[..., None]         # (nc, l, H, P)
    states = ops.mm(xd.reshape(nc, l, H * P).transpose(0, 2, 1), B)
    states = states.reshape(nc, H, P, N)
    # 3. the inter-chunk recurrence, as a matrix of chunk decays
    states = jnp.concatenate([jnp.zeros((1, H, P, N)), states])
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[:, :, -1], ((0, 0), (1, 0)))))
    new = ops.mm(decay_chunk,                                   # (H, nc+1, nc+1)
                 states.transpose(1, 0, 2, 3).reshape(H, nc + 1, P * N))
    prev = new[:, :-1].reshape(H, nc, P, N)                     # into each chunk
    # 4. the carried-in state to each position's output
    y_off = ops.mm(C, prev.transpose(1, 3, 0, 2).reshape(nc, N, H * P))
    y_off = y_off.reshape(nc, l, H, P) * jnp.exp(a_cs).transpose(1, 2, 0)[..., None]
    y = y_diag.transpose(1, 2, 0, 3) + y_off
    return y.reshape(s, H, P)
