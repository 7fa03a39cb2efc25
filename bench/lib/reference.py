"""The plain reference: a dense GPT (LayerNorm, rotary positions, causal
multi-head attention, tanh-GELU MLP, untied output head) written out in
``jax.numpy`` from the configuration's sizes, in float32 at the highest
matmul precision, with no kernels, caches or batching tricks.  It imports
nothing of the program.

Weights are made here from the seed, by the recipe the benchmark states
for every configuration (``init_params``): one key per leaf, split from
``PRNGKey(key_seed)`` in the sorted order of the parameter tree; matrices
normal with standard deviation 1/sqrt(fan-in), the embedding and output
head with 0.02, LayerNorm scales one and biases zero.  The program's
``Model.init`` follows the same recipe, so the program under test and the
reference start from the same numbers without sharing an array.

``Numerics`` chooses how matrix products are computed: ``FP32`` is the
reference; ``FP8`` rounds both operands of every product, forward and
backward, to float8 e4m3 with a per-tensor scale, which is the control
that the comparison must reject.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

_GELU_C = 0.044715
_SQRT_2_OVER_PI = 0.7978845608028654


# ---------------------------------------------------------------------------
# Products
# ---------------------------------------------------------------------------

def _exact(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


_E4M3_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return _exact(spec, _fp8(a), _fp8(b))


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: _exact(spec, x, y), _fp8(a), _fp8(b))
    da, db = vjp(_fp8(g))
    return da, db


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    einsum: Callable[[str, jax.Array, jax.Array], jax.Array]


FP32 = Numerics("fp32", _exact)
FP8 = Numerics("fp8", _fp8_einsum)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def param_leaves(cfg: dict) -> list[tuple[str, tuple[int, ...], str, float]]:
    """(path, shape, init, std) of every leaf, in the sorted order in which
    the keys are handed out."""
    L, d, ff, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    mat = lambda fan_in: 1.0 / np.sqrt(fan_in)  # noqa: E731
    return [
        ("embed", (V, d), "normal", 0.02),
        ("final_norm/bias", (d,), "zeros", 0.0),
        ("final_norm/scale", (d,), "ones", 0.0),
        ("layers/attn/ln/bias", (L, d), "zeros", 0.0),
        ("layers/attn/ln/scale", (L, d), "ones", 0.0),
        ("layers/attn/wk", (L, d, hkv), "normal", mat(d)),
        ("layers/attn/wo", (L, hq, d), "normal", mat(hq)),
        ("layers/attn/wq", (L, d, hq), "normal", mat(d)),
        ("layers/attn/wv", (L, d, hkv), "normal", mat(d)),
        ("layers/mlp/ln/bias", (L, d), "zeros", 0.0),
        ("layers/mlp/ln/scale", (L, d), "ones", 0.0),
        ("layers/mlp/w1", (L, d, ff), "normal", mat(d)),
        ("layers/mlp/w2", (L, ff, d), "normal", mat(ff)),
        ("lm_head", (d, V), "normal", 0.02),
    ]


def init_params(cfg: dict, key_seed: int, dtype=jnp.float32,
                mesh=None) -> dict:
    """Flat {path: array} of the weights, made on the device in one jitted
    call; ``dtype`` is the type they are stored in (the values are rounded
    to it and returned as float32).  With ``mesh``, each leaf is laid out
    over its devices by :func:`leaf_shardings`."""
    shardings = leaf_shardings(cfg, mesh) if mesh is not None else None
    return jax.jit(lambda key: init_params_traced(cfg, key, dtype),
                   out_shardings=shardings)(jax.random.PRNGKey(key_seed))


def flat_mesh(devices):
    """A one-axis mesh (``"x"``) over ``devices``, to lay out a reference
    that one chip cannot hold."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices), ("x",))


def leaf_shardings(cfg: dict, mesh) -> dict:
    """Each leaf split along its largest dimension that the mesh divides
    (replicated where none does).  Layout only: every value is the same as
    on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    out = {}
    for path, shape, _, _ in param_leaves(cfg):
        dims = [i for i, s in enumerate(shape) if s % n == 0]
        spec = [None] * len(shape)
        if dims:
            spec[max(dims, key=lambda i: shape[i])] = "x"
        out[path] = NamedSharding(mesh, P(*spec))
    return out


def batch_sharding(mesh, rows: int):
    """Rows of a token batch split over the mesh, where it divides them."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P("x" if rows % mesh.devices.size == 0
                                 else None))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, theta):
    """Rotate (B, S, H, hd) by position: the first and second halves of
    each head are the two coordinates of hd/2 planes."""
    hd, S = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(_SQRT_2_OVER_PI * (x + _GELU_C * x ** 3)))


def attention(q, k, v, num: Numerics, q_block: int):
    """Causal softmax attention on (B, S, H, hd), a block of queries at a
    time so that the scores of one block are all that is held."""
    B, S, H, hd = q.shape
    outs = []
    for s0 in range(0, S, q_block):
        qb = q[:, s0:s0 + q_block]
        sc = num.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(hd)
        qpos = np.arange(s0, s0 + qb.shape[1])[:, None]
        mask = jnp.asarray(np.arange(S)[None, :] <= qpos)
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(num.einsum("bhqk,bkhd->bqhd", p, v))
    return jnp.concatenate(outs, axis=1)


def block(x, lp: dict, cfg: dict, num: Numerics, q_block: int):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    h = layer_norm(x, lp["attn/ln/scale"], lp["attn/ln/bias"], eps)
    q = num.einsum("bsd,de->bse", h, lp["attn/wq"]).reshape(B, S, H, hd)
    k = num.einsum("bsd,de->bse", h, lp["attn/wk"]).reshape(B, S, Hkv, hd)
    v = num.einsum("bsd,de->bse", h, lp["attn/wv"]).reshape(B, S, Hkv, hd)
    q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    o = attention(q, k, v, num, q_block).reshape(B, S, H * hd)
    x = x + num.einsum("bse,ed->bsd", o, lp["attn/wo"])
    h = layer_norm(x, lp["mlp/ln/scale"], lp["mlp/ln/bias"], eps)
    u = gelu(num.einsum("bsd,df->bsf", h, lp["mlp/w1"]))
    return x + num.einsum("bsf,fd->bsd", u, lp["mlp/w2"])


def _layers(params: dict) -> dict:
    return {k[len("layers/"):]: v for k, v in params.items()
            if k.startswith("layers/")}


def hidden(params: dict, tokens, cfg: dict, num: Numerics,
           q_block: int = 512):
    """Final-normed hidden states (B, S, d) of ``tokens``."""
    x = params["embed"][tokens]
    body = jax.checkpoint(
        lambda c, lp: (block(c, lp, cfg, num, q_block), None))
    x, _ = jax.lax.scan(body, x, _layers(params))
    return layer_norm(x, params["final_norm/scale"], params["final_norm/bias"],
                      cfg["norm_eps"])


def logits(params: dict, tokens, cfg: dict, num: Numerics = FP32,
           q_block: int = 512):
    """(B, S, V) next-token logits."""
    return num.einsum("bsd,dv->bsv", hidden(params, tokens, cfg, num, q_block),
                      params["lm_head"])


def mean_loss(params: dict, tokens, cfg: dict, num: Numerics = FP32,
              q_block: int = 512):
    """Mean next-token cross-entropy over every position but the last."""
    z = logits(params, tokens, cfg, num, q_block)[:, :-1]
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# Training: three AdamW steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float

    @classmethod
    def from_dict(cls, d: dict) -> "AdamW":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_steps(cfg: dict, key_seed: int, batches: list[np.ndarray],
                opt: AdamW, num: Numerics = FP32, mesh=None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.

    Returns host values: ``losses`` (one per step, before its update),
    ``grad`` (per leaf, the norm of the first step's gradient after global
    clipping, as the optimizer takes it), ``raw_grad`` (the same before
    clipping) and ``change`` (per leaf, the norm of the weights' change
    after the last step).  Weight decay applies to every leaf of two or more
    dimensions, the layer-stacked LayerNorm leaves among them.  With
    ``mesh`` the weights, the optimizer state and the batch are laid out
    over its devices (:func:`leaf_shardings`), and the gradient is taken a
    block of rows (one per device) at a time, so that only one block's
    activations are held; every row has as many positions, so the mean
    over blocks is the mean over rows.
    """
    params = init_params(cfg, key_seed, mesh=mesh)
    place = (lambda t: jnp.asarray(t)) if mesh is None else (
        lambda t: jax.device_put(t, batch_sharding(mesh, t.shape[0])))

    def loss_and_grad(p, tokens):
        return jax.value_and_grad(mean_loss)(p, tokens, cfg, num)

    def blocked(p, tokens):
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = mesh.devices.size
        blocks = jax.lax.with_sharding_constraint(
            tokens.reshape(-1, n, tokens.shape[1]),
            NamedSharding(mesh, P(None, "x")))

        def body(acc, t):
            return jax.tree.map(jnp.add, acc, loss_and_grad(p, t)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        total, _ = jax.lax.scan(body, zero, blocks)
        return jax.tree.map(lambda x: x / blocks.shape[0], total)

    grad_fn = jax.jit(loss_and_grad) if mesh is None else jax.jit(
        blocked, out_shardings=(None, leaf_shardings(cfg, mesh)))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, mu, nu, count):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        clip = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-9))
        c1 = 1.0 - opt.b1 ** count
        c2 = 1.0 - opt.b2 ** count
        np_, nmu, nnu, gn = {}, {}, {}, {}
        for k in p:
            gk = g[k] * clip
            gn[k] = jnp.sqrt(jnp.sum(jnp.square(gk)))
            nmu[k] = opt.b1 * mu[k] + (1 - opt.b1) * gk
            nnu[k] = opt.b2 * nu[k] + (1 - opt.b2) * jnp.square(gk)
            upd = (nmu[k] / c1) / (jnp.sqrt(nnu[k] / c2) + opt.eps)
            if p[k].ndim >= 2:
                upd = upd + opt.weight_decay * p[k]
            np_[k] = p[k] - opt.lr * upd
        return np_, nmu, nnu, gn

    mu = {k: jnp.zeros_like(v, device=v.sharding) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v, device=v.sharding) for k, v in params.items()}
    losses, grad, raw = [], None, None
    for i, tokens in enumerate(batches):
        loss, g = grad_fn(params, place(tokens))
        losses.append(float(loss))
        if i == 0:
            raw = {k: float(v) for k, v in jax.device_get(_norms(g)).items()}
        params, mu, nu, gn = update(params, g, mu, nu, jnp.float32(i + 1))
        del g
        if i == 0:
            grad = {k: float(v) for k, v in jax.device_get(gn).items()}
    del mu, nu
    delta = _change_norms(cfg, key_seed, params, mesh)
    return {"losses": losses, "grad": grad, "raw_grad": raw, "change": delta}


def _change_norms(cfg: dict, key_seed: int, params: dict, mesh=None) -> dict:
    """Per leaf, the norm of ``params`` minus the seed's initial weights,
    which are made anew inside the call rather than kept."""
    shardings = leaf_shardings(cfg, mesh) if mesh is not None else None

    @jax.jit
    def f(p, key):
        p0 = init_params_traced(cfg, key)
        if shardings is not None:
            p0 = {k: jax.lax.with_sharding_constraint(v, shardings[k])
                  for k, v in p0.items()}
        return {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k]))) for k in p}
    delta = f(params, jax.random.PRNGKey(key_seed))
    return {k: float(v) for k, v in jax.device_get(delta).items()}


def init_params_traced(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """:func:`init_params` inside a traced function, from the key
    ``PRNGKey(key_seed)`` passed in as an argument (a key made inside would
    be a constant of the program, and every seed would compile anew)."""
    leaves = param_leaves(cfg)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for (path, shape, init, std), k in zip(leaves, keys):
        if init == "zeros":
            x = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            x = jnp.ones(shape, jnp.float32)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
        out[path] = x.astype(dtype).astype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Serving: how far below the best each served token lies
# ---------------------------------------------------------------------------

def served_gaps(params: dict, cfg: dict, prompt: np.ndarray,
                served: np.ndarray, pad_to: int,
                control: Numerics | None = None) -> dict:
    """Run the reference once over ``prompt`` followed by ``served`` (padded
    at the end to ``pad_to``, which the causal mask keeps out of every real
    position).  For each served token, the gap by which its reference logit
    lies below the reference's best at that position.  With ``control``,
    also the gap of the token that the control's logits put first."""
    seq = np.zeros((1, pad_to), np.int32)
    full = np.concatenate([prompt, served]).astype(np.int32)
    seq[0, :len(full)] = full
    pos = np.arange(len(prompt) - 1, len(full) - 1)   # predicting served[i]
    out = _gap_fn(cfg, control)(params, jnp.asarray(seq), jnp.asarray(pos),
                                jnp.asarray(served.astype(np.int32)))
    return {k: np.asarray(v) for k, v in out.items()}


@functools.lru_cache(maxsize=8)
def _gap_fn_cached(cfg_items: tuple, control: Numerics | None):
    cfg = dict(cfg_items)

    @jax.jit
    def f(params, seq, pos, served):
        z = logits(params, seq, cfg)[0][pos]                 # (n, V)
        best = jnp.max(z, axis=-1)
        out = {"gap": best - jnp.take_along_axis(z, served[:, None], 1)[:, 0]}
        if control is not None:
            zc = logits(params, seq, cfg, control)[0][pos]
            first = jnp.argmax(zc, axis=-1)
            out["control_gap"] = best - jnp.take_along_axis(
                z, first[:, None], 1)[:, 0]
        return out
    return f


def _gap_fn(cfg: dict, control: Numerics | None):
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return _gap_fn_cached(items, control)
