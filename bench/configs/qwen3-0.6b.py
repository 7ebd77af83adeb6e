"""qwen3-0.6b: sizes, seeded weights, model FLOPs and the float32 reference.

The plain reference follows the published Qwen3 decoder: token embedding,
then per layer RMSNorm -> GQA attention with a per-head RMSNorm on queries
and keys and rotary embeddings (rotate-half, theta 1e6) -> residual, RMSNorm
-> SwiGLU MLP -> residual; a final RMSNorm and the tied embedding as output
head; mean next-token cross-entropy.  Every matmul goes through ``mm``, which
the caller sets to float32 at the highest precision (the reference) or to a
lower precision (the control).  It imports nothing of the program.
"""

import jax
import jax.numpy as jnp


def program_config(sizes):
    """The program's ModelConfig for these sizes (published widths; only the
    depth differs from the registry entry)."""
    import dataclasses

    from repro.configs.registry import get_config

    cfg = get_config(sizes["program_arch"])
    cfg = dataclasses.replace(
        cfg,
        n_layers=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        d_head=sizes["head_dim"], d_ff=sizes["intermediate_size"],
        vocab_size=sizes["vocab_size"], rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        dtype="bfloat16" if sizes["torch_dtype"] == "bfloat16" else "float32",
    )
    return cfg


def shapes(sizes):
    """Leaf shapes of the parameter tree, in the program's structure."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    n_layers = sizes["num_hidden_layers"]
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    f = sizes["intermediate_size"]
    return {
        "tok": {"embed": ((v, d), 0.02)},
        "final_norm": ((d,), None),
        "layers": {
            "ln1": ((n_layers, d), None),
            "ln2": ((n_layers, d), None),
            "attn": {
                "wq": ((n_layers, d, h * dh), d),
                "wk": ((n_layers, d, kv * dh), d),
                "wv": ((n_layers, d, kv * dh), d),
                "wo": ((n_layers, h * dh, d), h * dh),
                "q_norm": ((n_layers, dh), None),
                "k_norm": ((n_layers, dh), None),
            },
            "ffn": {
                "wi": ((n_layers, d, f), d),
                "wg": ((n_layers, d, f), d),
                "wo": ((n_layers, f, d), f),
            },
        },
    }


def init_params(sizes, key, dtype):
    """Weights from ``key``: each leaf from its own fold of the key; scales
    are ones, an embedding has std 0.02 and a dense weight std 1/sqrt(fan_in)."""
    spec = shapes(sizes)
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    out = []
    for i, (shape, scale) in enumerate(leaves):
        if scale is None:
            out.append(jnp.ones(shape, dtype))
            continue
        std = scale if isinstance(scale, float) else scale ** -0.5
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
        out.append(w.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def model_flops(sizes, tokens: int, seq: int) -> float:
    """Forward and backward FLOPs of ``tokens`` training tokens in sequences
    of ``seq``: 6 x (parameters that enter a matmul, the tied head once) per
    token, plus attention's 12 * layers * heads * head_dim * seq per token
    (QK^T and PV, forward and backward, without the causal halving)."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    n_layers = sizes["num_hidden_layers"]
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    f = sizes["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    matmul_params = n_layers * per_layer + v * d
    attn = 12 * n_layers * h * dh * seq
    return float(tokens) * (6.0 * matmul_params + attn)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, dh): rotate-half rotary embedding at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def loss(params, batch, sizes, mm):
    """Mean next-token cross-entropy of one batch, float32 throughout.
    ``mm(spec, a, b)`` is the einsum every matmul goes through."""
    eps = float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_theta"])
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    emb = params["tok"]["embed"]
    x = emb[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    lay = params["layers"]
    for i in range(sizes["num_hidden_layers"]):
        at, ff = jax.tree_util.tree_map(lambda a: a[i], (lay["attn"], lay["ffn"]))
        hn = _rms(x, lay["ln1"][i], eps)
        q = mm("bsd,de->bse", hn, at["wq"]).reshape(b, s, h, dh)
        k = mm("bsd,de->bse", hn, at["wk"]).reshape(b, s, kv, dh)
        v = mm("bsd,de->bse", hn, at["wv"]).reshape(b, s, kv, dh)
        q = _rope(_rms(q, at["q_norm"], eps), theta)
        k = _rope(_rms(k, at["k_norm"], eps), theta)
        # query head j reads key/value head j // (h // kv)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        att = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * dh)
        x = x + mm("bse,ed->bsd", att, at["wo"])
        hn = _rms(x, lay["ln2"][i], eps)
        gate = jax.nn.silu(mm("bsd,df->bsf", hn, ff["wg"]))
        up = mm("bsd,df->bsf", hn, ff["wi"])
        x = x + mm("bsf,fd->bsd", gate * up, ff["wo"])
    x = _rms(x, params["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, emb)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
