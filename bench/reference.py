"""Plain float32 reference of one federated round, and the comparison.

What a round does, as the paper states it (arXiv:2111.15071, Sec. III-IV),
written here without any of the program's code, tables or weights:

1. each client k takes the gradient g_k of its batch's mean loss at the
   server's parameters (the configuration's own ``loss``), in float32;
2. the gradient is cut into blocks of N entries (each leaf, and each part of
   a leaf that the wire sends apart, padded to whole blocks), the client's
   error-feedback residual is added, each block keeps its S largest
   magnitudes and the rest becomes the new residual;
3. each kept block is projected by the shared Gaussian matrix A (M x N,
   entries N(0, 1/M) from the protocol seed), scaled by alpha = sqrt(M) /
   ||block|| and quantized by the Q-bit Lloyd-Max quantizer of N(0, 1);
4. the server decodes: AE Bussgang-combines the clients' dequantized
   measurements and runs one EM-GAMP (Bernoulli Gaussian-mixture prior, L
   components, scalar variances, a fixed count of iterations) per block;
   EA runs one quantized-channel EM-GAMP per client and block and sums the
   estimates with the clients' weights.  Each estimate is clipped to twice
   its expected norm;
5. FedAdam applies the decoded aggregate (constant step, bias-corrected
   moments in float32); the parameters are stored in the configuration's
   dtype.

Every matmul of the model goes through ``mm``: float32 at the highest
precision for the reference, or operands and cotangents rounded to int8 for
the control (``precision="int8"``: the step below the configuration's
bfloat16 that the v5e's int8 MXU path would tempt).
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-12
# Bound on the scalars of one part of a leaf that the wire sends apart; a
# leaf (or a layer chunk of a stacked leaf) above it is cut along axis 0.
MAX_PART = 1 << 24
# Standardized distance past which a quantizer bin lies wholly in one tail
# of N(phat, nu_p); the posterior there is taken at the bin's near edge.
TAIL = 9.0


# ---------------------------------------------------------------------------
# Matmul precision: the reference and its control
# ---------------------------------------------------------------------------


def mm_f32(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _int8(x):
    """Symmetric per-tensor int8 rounding (scale max|x| / 127), as an int8
    matmul sees its operands."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def int8(x):
    """int8 rounding of a matmul operand in the forward pass and of its
    cotangent in the backward pass, each with its own per-tensor scale."""
    return _int8(x)


int8.defvjp(lambda x: (_int8(x), None), lambda _, g: (_int8(g),))


def mm_int8(spec, a, b):
    return jnp.einsum(spec, int8(a), int8(b), precision=HIGHEST)


MATMULS = {"f32": mm_f32, "int8": mm_int8}


# ---------------------------------------------------------------------------
# Protocol constants: Lloyd-Max quantizer and sensing matrix
# ---------------------------------------------------------------------------


def lloyd_max(bits: int, iters: int = 20000):
    """Q-bit MMSE quantizer of N(0, 1) by the Lloyd fixed point, in float64.
    Returns (levels, interior thresholds, gamma = E[Q(X) X], psi =
    E[Q(X)^2])."""
    nd = statistics.NormalDist()
    n = 1 << bits
    levels = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    pdf = np.vectorize(nd.pdf)
    cdf = np.vectorize(nd.cdf)
    for _ in range(iters):
        taus = 0.5 * (levels[:-1] + levels[1:])
        lo = np.concatenate([[-np.inf], taus])
        hi = np.concatenate([taus, [np.inf]])
        new = (pdf(lo) - pdf(hi)) / (cdf(hi) - cdf(lo))
        if np.max(np.abs(new - levels)) < 1e-14:
            levels = new
            break
        levels = new
    taus = 0.5 * (levels[:-1] + levels[1:])
    lo = np.concatenate([[-np.inf], taus])
    hi = np.concatenate([taus, [np.inf]])
    gamma = float(np.sum(levels * (pdf(lo) - pdf(hi))))
    psi = float(np.sum(levels**2 * (cdf(hi) - cdf(lo))))
    return levels, taus, gamma, psi


def sensing(seed: int, m: int, n: int):
    """A (M, N), entries N(0, 1/M), from the protocol seed."""
    return jax.random.normal(jax.random.PRNGKey(seed), (m, n), jnp.float32) / math.sqrt(m)


# ---------------------------------------------------------------------------
# Blocking: a tree <-> an (rows, N) grid, one run of rows per part of a leaf
# ---------------------------------------------------------------------------


def _bounds(total: int, parts: int) -> List[Tuple[int, int]]:
    parts = max(1, min(int(parts), total))
    base, rem = divmod(total, parts)
    out, lo = [], 0
    for i in range(parts):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


class Blocking:
    """The wire's blocks of a parameter-shaped tree.  A leaf of the stacked
    layers is sent in ``layer_chunks`` near-even parts along its layer axis;
    any part above ``MAX_PART`` scalars is cut further along axis 0; each
    part is flattened row-major and zero-padded to whole blocks of N.  Every
    block lies in one part, so blocks never mix two leaves."""

    def __init__(self, tree, n: int, layer_chunks: int):
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.n = n
        self.shapes = [tuple(x.shape) for _, x in flat]
        self.names = [jax.tree_util.keystr(p) for p, _ in flat]
        self.pieces = []  # (leaf, lo, hi, size, rows)
        for i, shape in enumerate(self.shapes):
            size = math.prod(shape)
            if not shape:
                self.pieces.append((i, None, None, 1, 1))
                continue
            row = size // shape[0]
            base = (_bounds(shape[0], layer_chunks)
                    if layer_chunks > 1 and self.names[i].startswith("['layers']")
                    else [(0, shape[0])])
            for lo, hi in base:
                k = -(-(hi - lo) * row // MAX_PART)
                for a, b in _bounds(hi - lo, k):
                    sz = (b - a) * row
                    self.pieces.append((i, lo + a, lo + b, sz, -(-sz // n)))
        self.rows = sum(p[4] for p in self.pieces)

    def to_grid(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        out = []
        for i, lo, hi, size, rows in self.pieces:
            x = leaves[i] if lo is None else leaves[i][lo:hi]
            x = x.reshape(-1).astype(jnp.float32)
            out.append(jnp.pad(x, (0, rows * self.n - size)).reshape(rows, self.n))
        return jnp.concatenate(out, axis=0)

    def from_grid(self, grid):
        parts: Dict[int, list] = {}
        r = 0
        for i, lo, hi, size, rows in self.pieces:
            flat = grid[r : r + rows].reshape(-1)[:size]
            shape = self.shapes[i] if lo is None else (hi - lo,) + self.shapes[i][1:]
            parts.setdefault(i, []).append(flat.reshape(shape))
            r += rows
        leaves = [
            parts[i][0] if len(parts[i]) == 1 else jnp.concatenate(parts[i], axis=0)
            for i in range(len(self.shapes))
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


# ---------------------------------------------------------------------------
# Client encode
# ---------------------------------------------------------------------------


def encode(grid, residual, a, thresholds, s: int):
    """One client's blocks -> (codes (rows, M) int32, alpha (rows,), new
    residual (rows, N)).  Keeps each block's ``s`` largest magnitudes."""
    carry = grid + residual
    mag = jnp.abs(carry)
    kth = jax.lax.top_k(mag, s)[0][:, -1:]
    sparse = jnp.where(mag >= kth, carry, 0.0)
    norm = jnp.sqrt(jnp.sum(sparse * sparse, axis=-1))
    m = a.shape[0]
    alpha = jnp.where(norm > 1e-20, math.sqrt(m) / jnp.where(norm > 1e-20, norm, 1.0), 0.0)
    x = jnp.matmul(sparse, a.T, precision=HIGHEST) * alpha[:, None]
    codes = jnp.sum(x[..., None] > thresholds, axis=-1).astype(jnp.int32)
    return codes, alpha, carry - sparse


# ---------------------------------------------------------------------------
# Server decode: EM-GAMP with a Bernoulli Gaussian-mixture prior
# ---------------------------------------------------------------------------


def _gauss(x, mean, var):
    return jnp.exp(-0.5 * (x - mean) ** 2 / var) / jnp.sqrt(2.0 * jnp.pi * var)


def _prior_posterior(rhat, nu_r, theta):
    """Posterior of g given rhat = g + N(0, nu_r), g ~ BG-mixture(theta);
    returns (mean, variance, new theta by one EM step)."""
    lam0, lam, mu, phi = theta  # (R,1), (R,L) x3; nu_r (R,1)
    v = nu_r[..., None]  # (R,1,1)
    r = rhat[..., None]  # (R,N,1)
    var = jnp.maximum(v + phi[:, None, :], EPS)
    b0 = lam0 * _gauss(rhat, 0.0, nu_r)  # (R,N)
    b = lam[:, None, :] * _gauss(r, mu[:, None, :], var)  # (R,N,L)
    den = jnp.maximum(b0 + jnp.sum(b, -1), EPS)
    p0 = b0 / den
    p = b / den[..., None]
    mpost = (r * phi[:, None, :] + mu[:, None, :] * v) / var
    vpost = v * phi[:, None, :] / var
    mean = jnp.sum(p * mpost, -1)
    second = jnp.sum(p * (vpost + mpost * mpost), -1)
    variance = jnp.maximum(second - mean * mean, EPS)
    # EM: weights, means and the scatter about the new means
    n = rhat.shape[1]
    psum = jnp.sum(p, axis=1)  # (R,L)
    safe = jnp.maximum(psum, EPS)
    mu_new = jnp.sum(p * mpost, axis=1) / safe
    phi_new = jnp.sum(p * ((mu_new[:, None, :] - mpost) ** 2 + vpost), axis=1) / safe
    lam0_new = jnp.clip(jnp.mean(p0, axis=1, keepdims=True), 1e-6, 1.0 - 1e-6)
    lam_new = jnp.maximum(psum / n, 1e-8)
    total = jnp.maximum(lam0_new + jnp.sum(lam_new, -1, keepdims=True), EPS)
    return mean, variance, (lam0_new / total, lam_new / total, mu_new,
                            jnp.maximum(phi_new, EPS))


def _awgn(y, nu_d):
    def channel(phat, nu_p):
        post = (phat * nu_d + y * nu_p) / (nu_p + nu_d)
        return post, nu_p * nu_d / (nu_p + nu_d)
    return channel


def _erfc(x):
    return jax.scipy.special.erfc(x)


def _quantized(lo, hi):
    """Posterior of x ~ N(phat, nu_p) known to lie in (lo, hi]: the moments
    of the truncated normal; a bin wholly beyond ``TAIL`` standard deviations
    gives the mean just inside its near edge and the tail variance."""
    def channel(phat, nu_p):
        sd = jnp.sqrt(nu_p)
        a = (lo - phat) / sd
        b = (hi - phat) / sd
        far = (a > TAIL) | (b < -TAIL)
        ac, bc = jnp.clip(a, -TAIL, TAIL), jnp.clip(b, -TAIL, TAIL)
        r2 = 1.0 / math.sqrt(2.0)
        # bin mass as a difference of small numbers in either tail
        mass = jnp.where(ac > 0, 0.5 * (_erfc(ac * r2) - _erfc(bc * r2)),
                         0.5 * (_erfc(-bc * r2) - _erfc(-ac * r2)))
        mass = jnp.maximum(mass, 1e-30)
        pa = jnp.exp(-0.5 * ac * ac) / math.sqrt(2 * math.pi)
        pb = jnp.exp(-0.5 * bc * bc) / math.sqrt(2 * math.pi)
        k1 = (pa - pb) / mass
        k2 = (ac * pa - bc * pb) / mass
        mean = phat + sd * k1
        var = nu_p * jnp.maximum(1.0 + k2 - k1 * k1, 1e-8)
        amin = jnp.minimum(jnp.abs(a), jnp.abs(b))
        inward = jnp.where(phat < lo, 1.0, -1.0)
        mean_far = jnp.clip(phat, lo, hi) + inward * sd / jnp.maximum(amin, 1.0)
        var_far = nu_p / jnp.maximum(amin * amin, 1.0)
        mean = jnp.where(far, mean_far, mean)
        var = jnp.minimum(jnp.where(far, var_far, var), nu_p)
        return mean, var
    return channel


def gamp(channel, a, scale, init_var, iters: int, comps: int, lam0: float):
    """EM-GAMP over rows: the measurement of a row is ``scale * A g`` seen
    through ``channel``; scalar (row-mean) variances; ``iters`` iterations.
    Returns the estimates (R, N) before the norm clip."""
    m, n = a.shape
    rows = scale.shape[0]
    sc = scale[:, None]
    s2 = sc * sc
    sigma = jnp.sqrt(jnp.maximum(init_var, EPS))[:, None]
    ls = jnp.arange(1, comps + 1, dtype=jnp.float32)[None, :]
    mu = -3.0 * sigma + (2.0 * ls - 1.0) / (2.0 * comps) * 6.0 * sigma
    phi = jnp.broadcast_to((6.0 * sigma / comps) ** 2 / 12.0, mu.shape)
    theta = (jnp.full((rows, 1), lam0, jnp.float32),
             jnp.full((rows, comps), (1.0 - lam0) / comps, jnp.float32), mu, phi)
    ghat = jnp.zeros((rows, n), jnp.float32)
    nu_g = jnp.broadcast_to(jnp.maximum(init_var, EPS)[:, None], (rows, n))
    shat = jnp.zeros((rows, m), jnp.float32)

    def body(_, carry):
        ghat, nu_g, shat, theta = carry
        nu_p = jnp.maximum(s2 / m * jnp.sum(nu_g, -1, keepdims=True), EPS)
        phat = sc * jnp.matmul(ghat, a.T, precision=HIGHEST) - nu_p * shat
        post, nu_x = channel(phat, nu_p)
        shat = (post - phat) / nu_p
        nu_s = jnp.maximum((1.0 - nu_x / nu_p) / nu_p, EPS)
        nu_s = jnp.broadcast_to(nu_s, phat.shape)
        nu_r = 1.0 / jnp.maximum(s2 / m * jnp.sum(nu_s, -1, keepdims=True), EPS)
        rhat = ghat + nu_r * sc * jnp.matmul(shat, a, precision=HIGHEST)
        ghat, nu_g, theta = _prior_posterior(rhat, nu_r, theta)
        return ghat, nu_g, shat, theta

    ghat, _, _, _ = jax.lax.fori_loop(0, iters, body, (ghat, nu_g, shat, theta))
    return ghat


def clip_norm(ghat, expected):
    """Each row clipped to twice its expected norm."""
    norm = jnp.sqrt(jnp.sum(ghat * ghat, -1))
    return ghat * jnp.minimum(1.0, 2.0 * expected / jnp.maximum(norm, 1e-30))[:, None]


def _by_chunks(fn, arrays: Sequence[jnp.ndarray], chunk: int):
    """``fn`` over row chunks of ``arrays`` (rows padded to whole chunks)."""
    rows = arrays[0].shape[0]
    k = -(-rows // chunk)
    pad = k * chunk - rows
    xs = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape((k, chunk) + x.shape[1:])
          for x in arrays]
    out = jax.lax.map(lambda args: fn(*args), xs)
    return out.reshape((k * chunk,) + out.shape[2:])[:rows]


def decode_ae(codes, alphas, rhos, q, a, gp, chunk):
    """AE: Bussgang-combine then one EM-GAMP per block -> (rows, N)."""
    levels, gamma, psi = q["levels"], q["gamma"], q["psi"]
    kappa = (psi - gamma**2) / gamma**2
    m, n = a.shape
    live = alphas > 0
    safe = jnp.where(live, alphas, 1.0)
    w = jnp.where(live, rhos[:, None] / (gamma * safe), 0.0)
    y = jnp.sum(w[..., None] * levels[codes], axis=0)
    nu = kappa * jnp.sum(jnp.where(live, (rhos[:, None] / safe) ** 2, 0.0), axis=0)
    energy = jnp.sum(jnp.where(live, rhos[:, None] ** 2 * m / safe**2, 0.0), axis=0) / n

    def solve(y, nu, energy):
        g = gamp(_awgn(y, jnp.maximum(nu, EPS)[:, None]), a, jnp.ones_like(nu), energy,
                 gp["iters"], gp["comps"], gp["lam0"])
        return clip_norm(g, jnp.sqrt(jnp.maximum(energy * n, 0.0)))

    return _by_chunks(solve, (y, nu, energy), chunk)


def decode_ea(codes, alphas, rhos, q, a, gp, chunk):
    """EA: one quantized-channel EM-GAMP per client and block, then the
    clients' weighted sum -> (rows, N)."""
    m, n = a.shape
    big = np.array([4.0 * TAIL], np.float32)
    taus = np.asarray(q["thresholds"], np.float32)
    lo_t = jnp.asarray(np.concatenate([-big, taus]))
    hi_t = jnp.asarray(np.concatenate([taus, big]))

    def solve(codes, alpha):
        live = alpha > 0
        safe = jnp.where(live, alpha, 1.0)
        init_var = jnp.where(live, m / (n * safe**2), 1.0)
        g = gamp(_quantized(lo_t[codes], hi_t[codes]), a, safe, init_var,
                 gp["iters"], gp["comps"], gp["lam0"])
        g = jnp.where(live[:, None], g, 0.0)
        return clip_norm(g, jnp.where(live, math.sqrt(m) / safe, 0.0))

    total = 0.0
    for k in range(codes.shape[0]):
        total = total + rhos[k] * _by_chunks(solve, (codes[k], alphas[k]), chunk)
    return total


# ---------------------------------------------------------------------------
# Server optimizer
# ---------------------------------------------------------------------------


def adam(params, ghat, m, v, step: int, sv, stored):
    """FedAdam with a constant step: float32 moments; each parameter rounded
    to its stored dtype (the tree ``stored``) after the update."""
    t = step + 1
    b1, b2, lr, eps = sv["b1"], sv["b2"], sv["lr"], sv["eps"]

    def one(p, g, mm, vv, like):
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * g * g
        step_dir = (mm / (1 - b1**t)) / (jnp.sqrt(vv / (1 - b2**t)) + eps)
        p = (p - lr * step_dir).astype(like.dtype).astype(jnp.float32)
        return p, mm, vv

    out = jax.tree_util.tree_map(one, params, ghat, m, v, stored)
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out,
                                            is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


# ---------------------------------------------------------------------------
# The reference's first steps
# ---------------------------------------------------------------------------


def _norms_of(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


_norms = jax.jit(_norms_of)
_diff_norms = jax.jit(lambda tree, base: _norms_of(jax.tree_util.tree_map(
    lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), tree, base)))


def leaf_norms(tree) -> np.ndarray:
    """Per-leaf Euclidean norms, in float64 on the host (one fused program,
    no leaf-sized temporaries)."""
    return np.asarray(_norms(tree), np.float64)


def change_norms(tree, base) -> np.ndarray:
    """Per-leaf norms of ``tree - base``."""
    return np.asarray(_diff_norms(tree, base), np.float64)


def first_steps(cfgmod, sizes, traffic, params0, batches: Callable[[int], Any],
                steps: int, precision: str = "f32") -> Dict[str, Any]:
    """Runs ``steps`` rounds of the plain reference from ``params0`` (the
    seeded weights as stored) on ``batches(round)`` ((clients, batch, seq)
    token arrays).  Returns per-leaf norms: the first round's true mean
    gradient ``grad``, its decoded aggregate as FedAdam receives it
    ``ghat``, each client's residual after it ``residual`` (clients x
    leaves), and the parameters' change after ``steps`` rounds ``update``."""
    codec = traffic["codec"]
    n, r, bits = codec["block_size"], codec["reduction_ratio"], codec["bits"]
    m = n // r
    s = max(1, int(codec["s_ratio"] * n))
    clients = traffic["clients"]
    mm = MATMULS[precision]
    a = sensing(codec["sensing_seed"], m, n)
    levels, taus, gamma, psi = lloyd_max(bits)
    q = {"levels": jnp.asarray(levels, jnp.float32), "thresholds": taus,
         "gamma": gamma, "psi": psi}
    gp = {"iters": codec["gamp_iters"], "comps": codec["gamp_components"],
          "lam0": codec["lam0"]}
    chunk = codec["recon_chunk"]
    method = traffic["method"]
    accum = traffic.get("grad_accum", 1)

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params0)
    blocking = Blocking(params, n, traffic.get("interleave", 1))
    rhos = jnp.full((clients,), 1.0 / clients, jnp.float32)

    def client_grad(p, batch):
        def micro(b):
            return jax.grad(lambda pp: cfgmod.loss(pp, b, sizes, mm))(p)
        if accum == 1:
            return micro(batch)
        split = jax.tree_util.tree_map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch)
        total = jax.lax.fori_loop(
            0, accum,
            lambda i, acc: jax.tree_util.tree_map(
                jnp.add, acc, micro(jax.tree_util.tree_map(lambda x: x[i], split))),
            jax.tree_util.tree_map(jnp.zeros_like, p))
        return jax.tree_util.tree_map(lambda g: g / accum, total)

    grad_fn = jax.jit(client_grad)
    to_grid = jax.jit(blocking.to_grid)
    from_grid = jax.jit(blocking.from_grid)
    enc = jax.jit(lambda g, res: encode(g, res, a, jnp.asarray(taus, jnp.float32), s))
    dec = jax.jit(lambda c, al: (decode_ae if method == "fedqcs-ae" else decode_ea)(
        c, al, rhos, q, a, gp, chunk))
    stored = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params0)
    opt = jax.jit(lambda p, g, m1, v1, t: adam(p, g, m1, v1, t, traffic["server"], stored),
                  static_argnums=(4,))

    residual = [jnp.zeros((blocking.rows, n), jnp.float32) for _ in range(clients)]
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    v1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    out: Dict[str, Any] = {"names": blocking.names}
    for t in range(steps):
        batch = batches(t)
        codes, alphas, mean_grad = [], [], None
        for k in range(clients):
            g = grad_fn(params, jax.tree_util.tree_map(lambda x: x[k], batch))
            if t == 0:
                mean_grad = g if mean_grad is None else jax.tree_util.tree_map(
                    jnp.add, mean_grad, g)
            c, al, residual[k] = enc(to_grid(g), residual[k])
            codes.append(c)
            alphas.append(al)
            del g
        ghat = from_grid(dec(jnp.stack(codes), jnp.stack(alphas)))
        del codes, alphas
        if t == 0:
            out["grad"] = leaf_norms(mean_grad) / clients
            out["ghat"] = leaf_norms(ghat)
            out["residual"] = np.stack([leaf_norms(from_grid(res)) for res in residual])
            del mean_grad
        params, m1, v1 = opt(params, ghat, m1, v1, t)
    out["update"] = change_norms(params, params0)
    return out


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------

# Leaves whose true gradient is below this share of the median leaf's move
# under Adam by round-off alone; they are left out of the update's gap.
STILL_LEAF = 1e-3


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf's |‖prog‖ - ‖ref‖| over max(‖ref‖, median leaf's ‖ref‖)."""
    prog = np.atleast_2d(prog)
    ref = np.atleast_2d(ref)
    med = np.median(ref, axis=-1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if keep is not None:
        gap = gap[..., keep]
    return float(np.max(gap))


def _gaps(prog, ref):
    still = ref["grad"] < STILL_LEAF * np.median(ref["grad"])
    return {"grad_gap": ("ghat", None), "residual_gap": ("residual", None),
            "update_gap": ("update", ~still)}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers that decide ``correct``, each a worst-leaf gap of norms."""
    return {name: norm_gap(prog[key], ref[key], keep)
            for name, (key, keep) in _gaps(prog, ref).items()}


def norms_record(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-leaf norm the numbers are made from, for the record."""
    keys = ("ghat", "residual", "update")
    return {"names": ref["names"], "reference_grad": ref["grad"].tolist(),
            "program": {k: np.asarray(prog[k]).tolist() for k in keys},
            "reference": {k: np.asarray(ref[k]).tolist() for k in keys}}


def worst_leaves(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, str]:
    """For each number, the leaf that sets it, with both norms (a log line)."""
    out = {}
    for name, (key, keep) in _gaps(prog, ref).items():
        p, r = np.atleast_2d(prog[key]), np.atleast_2d(ref[key])
        med = np.median(r, axis=-1, keepdims=True)
        gap = np.abs(p - r) / np.maximum(np.maximum(r, med), 1e-30)
        if keep is not None:
            gap = np.where(keep, gap, -1.0)
        row, leaf = np.unravel_index(np.argmax(gap), gap.shape)
        out[name] = (f"{ref['names'][leaf]} program {float(p[row, leaf])!r} "
                     f"reference {float(r[row, leaf])!r} median {float(med[row, 0])!r}")
    return out
