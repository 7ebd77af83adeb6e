"""mamba2-1.3b: sizes, seeded weights, model FLOPs and the float32 reference.

The plain reference runs each Mamba-2 layer as the state-space model itself,
in its quadratic (dual) form over the whole sequence, with no chunking: in
projection to (z, x, B, C, dt); a causal depthwise convolution of width
d_conv over (x, B, C) and SiLU; dt = softplus(dt + dt_bias), A = -exp(a_log);

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < k <= t} dt_k A) dt_s x_s + D x_t

per head; a gated RMSNorm of y * silu(z); the out projection, added to the
residual stream.  A final RMSNorm and the tied embedding give the logits;
the loss is mean next-token cross-entropy.  The program's block adds the
mixer to the stream with no pre-mixer RMSNorm (the published model has one);
this reference follows the program there.  Every matmul goes through ``mm``.
It imports nothing of the program.
"""

import math

import jax
import jax.numpy as jnp


def program_config(sizes):
    import dataclasses

    from repro.configs.registry import get_config

    cfg = get_config(sizes["program_arch"])
    return dataclasses.replace(
        cfg, n_layers=sizes["n_layer"], d_model=sizes["d_model"],
        vocab_size=sizes["vocab_size"], ssm_state=sizes["d_state"],
        ssm_head_dim=sizes["headdim"], ssm_expand=sizes["expand"],
        ssm_conv_kernel=sizes["d_conv"], ssm_chunk=sizes["chunk_size"],
        norm_eps=float(sizes["rms_norm_eps"]), tie_embeddings=bool(sizes["tie_embeddings"]),
        dtype="bfloat16" if sizes["torch_dtype"] == "bfloat16" else "float32",
    )


def _dims(sizes):
    d = sizes["d_model"]
    di = sizes["expand"] * d
    n = sizes["d_state"]
    h = di // sizes["headdim"]
    return d, di, n, h


def init_params(sizes, key, dtype):
    """Weights from ``key`` in the program's tree; A, D and dt_bias are kept
    in float32, as the program keeps them."""
    d, di, n, h = _dims(sizes)
    layers, v, k = sizes["n_layer"], sizes["vocab_size"], sizes["d_conv"]
    ks = iter(jax.random.split(key, 16))
    nrm = lambda shape, std: (jax.random.normal(next(ks), shape, jnp.float32) * std)
    dt = jnp.exp(jax.random.uniform(next(ks), (layers, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "tok": {"embed": nrm((v, d), 0.02).astype(dtype)},
        "final_norm": jnp.ones((d,), dtype),
        "layers": {
            "in_proj": nrm((layers, d, 2 * di + 2 * n + h), d ** -0.5).astype(dtype),
            "conv_w": nrm((layers, k, di + 2 * n), 0.02).astype(dtype),
            "conv_b": jnp.zeros((layers, di + 2 * n), dtype),
            "a_log": jnp.log(jax.random.uniform(next(ks), (layers, h), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((layers, h), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "gate_norm": jnp.ones((layers, di), dtype),
            "out_proj": nrm((layers, di, d), di ** -0.5).astype(dtype),
        },
    }


def model_flops(sizes, tokens: int, seq: int) -> float:
    """Forward and backward FLOPs of ``tokens`` training tokens: 6 x
    (parameters that enter a matmul: in and out projections, the tied head
    once) per token, plus the scan's own work, counted in its recurrent
    form: per token, layer and head the state update (2 P N) and the
    output read (2 P N), forward and backward (x3), and the depthwise
    convolution (2 d_conv channels, x3).  ``seq`` does not enter."""
    d, di, n, h = _dims(sizes)
    p, k = sizes["headdim"], sizes["d_conv"]
    layers, v = sizes["n_layer"], sizes["vocab_size"]
    matmul_params = layers * (d * (2 * di + 2 * n + h) + di * d) + v * d
    scan = layers * 3 * (4 * p * n * h + 2 * k * (di + 2 * n))
    return float(tokens) * (6.0 * matmul_params + scan)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer(lp, x, sizes, mm):
    d, di, n, h = _dims(sizes)
    p, k = sizes["headdim"], sizes["d_conv"]
    b, t, _ = x.shape
    proj = mm("btd,de->bte", x, lp["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], proj[..., 2 * di + 2 * n:]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * lp["conv_w"][i] for i in range(k)) + lp["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(b, t, h, p)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # (b,t,h)
    cum = jnp.cumsum(dt * -jnp.exp(lp["a_log"]), axis=1)  # (b,t,h)
    seg = cum[:, :, None, :] - cum[:, None, :, :]  # (b,t,s,h): sum over (s, t]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = mm("btn,bsn->bts", cm, bm)
    w = cb[..., None] * decay * dt[:, None, :, :]  # (b,t,s,h)
    y = mm("btsh,bshp->bthp", w, xs) + xs * lp["d_skip"][:, None]
    y = _rms(y.reshape(b, t, di) * jax.nn.silu(z), lp["gate_norm"],
             float(sizes["rms_norm_eps"]))
    return x + mm("bte,ed->btd", y, lp["out_proj"])


def loss(params, batch, sizes, mm):
    """Mean next-token cross-entropy of one batch, float32 throughout; each
    layer is recomputed in the backward pass (its (t, t) decay is large)."""
    tokens, labels = batch["tokens"], batch["labels"]
    emb = params["tok"]["embed"]
    x = emb[tokens]
    lay = params["layers"]
    step = jax.checkpoint(lambda lp, x: _layer(lp, x, sizes, mm))
    for i in range(sizes["n_layer"]):
        x = step(jax.tree_util.tree_map(lambda a: a[i], lay), x)
    x = _rms(x, params["final_norm"], float(sizes["rms_norm_eps"]))
    logits = mm("btd,vd->btv", x, emb)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
