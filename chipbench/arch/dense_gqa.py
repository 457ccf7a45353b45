"""Dense GQA decoder (the Qwen3 layout): weights from a seed, the plain
float32 reference, and its fp8 control.

The reference follows the published layer equations of a pre-norm GQA
decoder with qk-norm (Qwen3):

    h = x + W_o · attn(RoPE(norm_q(W_q · rms(x))), RoPE(norm_k(W_k · rms(x))),
                       W_v · rms(x))
    y = h + W_2 · (silu(W_1 · rms(h)) ⊙ W_3 · rms(h))

with causal softmax attention, each kv head shared by ``H / K`` query
heads, and logits ``rms(y_L) · W_unembed``.  It imports nothing of the
program under test: it reads the parameter tree by its leaf names only
(``embed/embedding``, ``embed/unembed``, ``layers/attn/wq`` ...), widens
one layer at a time to float32, and runs every matmul at "highest"
precision (on a TPU a float32 matmul otherwise runs in bf16 passes).

``low=True`` is the control: the same equations with every matmul input
rounded to float8 e4m3 (one scale per tensor), the precision step below
the bf16 the configurations state.  Under a gradient the rounding of the
inputs passes the cotangent straight through, and each matmul's output
cotangent is rounded to float8 e5m2 with a scale of its own (the fp8
training recipe: e4m3 forward, e5m2 backward), so the backward matmuls
also take fp8 inputs and no gradient underflows for want of a scale.
A check that cannot tell the control from the reference cannot tell a
program that computed in fp8.

The training loss is the program's recipe: mean token cross-entropy
plus 1e-4 x mean log-sum-exp squared (z-loss), and AdamW with global
norm clipping, bias correction and decoupled weight decay at a constant
learning rate.

A sharded train cell's reference runs over the cell's chips by a
placement of its own, not the program's: the weights where ``spread``
puts them, the activations split by heads, FFN columns and vocabulary
(``_split``).  On one device both are no-ops.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.bench import leaf_name

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
FP8_MAX = 448.0                       # largest finite float8_e4m3fn
E5M2_MAX = 57344.0                    # largest finite float8_e5m2

# Leaves by name: how many leading dims (after the stacked layer dim)
# contract with the input.  Initialised N(0, 1/fan_in).
_FAN_IN_DIMS = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w1": 1, "w3": 1,
                "w2": 1, "unembed": 1}
_ONES = {"scale", "q_norm", "k_norm"}
_UNIT = {"embedding"}


# ---------------------------------------------------------------------------
# weights
def _leaf_std(name: str, shape, stacked: bool) -> float:
    last = name.rsplit("/", 1)[-1]
    if last in _ONES:
        return 0.0
    if last in _UNIT:
        return 1.0
    if last not in _FAN_IN_DIMS:
        raise ValueError(f"dense_gqa: no initialiser for leaf {name}")
    dims = shape[1:] if stacked else shape
    return 1.0 / math.sqrt(math.prod(dims[:_FAN_IN_DIMS[last]]))


def make_params(abstract, seed32: int, dtype, out_shardings=None):
    """Every leaf of ``abstract`` (a tree of ShapeDtypeStructs) drawn
    from ``seed32`` in one jitted call, on the device, in ``dtype``.
    Stacked layer leaves are drawn one layer at a time (``lax.map``), so
    the transient is one layer's leaf and not the whole stack."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in flat]
    shapes = [tuple(a.shape) for _, a in flat]

    def draw(key):
        out = []
        for i, (name, shape) in enumerate(zip(names, shapes)):
            stacked = name.startswith("layers/")
            std = _leaf_std(name, shape, stacked)
            k = jax.random.fold_in(key, i)
            if std == 0.0:
                out.append(jnp.ones(shape, dtype))
            elif stacked:
                ks = jax.random.split(k, shape[0])
                out.append(jax.lax.map(
                    lambda kk: (std * jax.random.normal(kk, shape[1:], F32)
                                ).astype(dtype), ks))
            else:
                out.append((std * jax.random.normal(k, shape, F32)
                            ).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(draw, out_shardings=out_shardings)
    return fn(jax.random.key(seed32))


def spread(abstract, devices):
    """The reference's own placement over several devices, apart from
    the program's rules: each leaf split along its largest axis that the
    device count divides (replicated where none does), on a 1-D mesh.
    The jitted steps below then run over all of them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devices), ("d",))
    n = len(devices)

    def one(a):
        spec = [None] * len(a.shape)
        fits = [i for i, d in enumerate(a.shape) if d % n == 0]
        if fits:
            spec[max(fits, key=lambda i: a.shape[i])] = "d"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(one, abstract)


# ---------------------------------------------------------------------------
# numerics
def _round8(t, dtype, top):
    """Round to ``dtype`` (a float8) with one scale per tensor."""
    s = jnp.max(jnp.abs(t)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (t / s).astype(dtype).astype(t.dtype) * s


@jax.custom_vjp
def _q8(t):
    """A matmul input in float8 e4m3; its cotangent passes unrounded."""
    return _round8(t, jnp.float8_e4m3fn, FP8_MAX)


_q8.defvjp(lambda t: (_q8(t), None), lambda _, ct: (ct,))


@jax.custom_vjp
def _g8(t):
    """The identity; its cotangent is rounded to float8 e5m2."""
    return t


_g8.defvjp(lambda t: (t, None),
           lambda _, ct: (_round8(ct, jnp.float8_e5m2, E5M2_MAX),))


def _mm(eq, a, b, low):
    if low:
        return _g8(jnp.einsum(eq, _q8(a), _q8(b), precision=HIGHEST))
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _split(x, axis: int):
    """Lay an activation out split along ``axis`` over the reference's
    devices, where it runs on several (``train_steps`` sets their mesh);
    on one device, x as it is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = [None] * x.ndim
    spec[axis] = mesh.axis_names[0]
    return jax.lax.with_sharding_constraint(x, jax.sharding.PartitionSpec(
        *spec))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (B, S, H, d); pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None, None] * freqs              # (S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attend_block(qb, k, v, lo, hd, low):
    """Causal softmax attention of query rows [lo, lo + len(qb)) over
    keys k, v (B, T, H, d)."""
    s = _mm("bshk,bthk->bhst", qb, k, low) / math.sqrt(hd)
    T = k.shape[1]
    causal = (lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(T)[None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    return _mm("bhst,bthk->bshk", jax.nn.softmax(s, -1), v, low)


def _layer(p, x, *, spec, q_block: int, low: bool, train: bool):
    """One decoder layer on x (B, S, D) float32."""
    p = jax.tree.map(lambda a: a.astype(F32), p)
    S = x.shape[1]
    eps, hd = spec["rms_norm_eps"], spec["head_dim"]
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _rms(_mm("bsd,dhk->bshk", h, a["wq"], low), a["q_norm"], eps)
    k = _rms(_mm("bsd,dhk->bshk", h, a["wk"], low), a["k_norm"], eps)
    v = _mm("bsd,dhk->bshk", h, a["wv"], low)
    q = _split(_rope(q, pos, spec["rope_theta"]), 2)
    k = _rope(k, pos, spec["rope_theta"])
    groups = spec["num_attention_heads"] // spec["num_key_value_heads"]
    k = _split(jnp.repeat(k, groups, axis=2), 2)       # head h -> h // G
    v = _split(jnp.repeat(v, groups, axis=2), 2)
    attend = jax.checkpoint(_attend_block, static_argnums=(3, 4, 5)) \
        if train else _attend_block
    o = _split(jnp.concatenate([attend(q[:, lo:lo + q_block], k, v, lo, hd,
                                       low)
                                for lo in range(0, S, q_block)], 1), 2)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], low)
    h = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = _split(jax.nn.silu(_mm("bsd,df->bsf", h, m["w1"], low))
               * _mm("bsd,df->bsf", h, m["w3"], low), 2)
    return x + _mm("bsf,fd->bsd", g, m["w2"], low)


_layer_jit = jax.jit(_layer, static_argnames=("spec", "q_block", "low",
                                              "train"))


SIZES = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
         "rms_norm_eps", "rope_theta")


class Spec(dict):
    """A configuration's sizes as a hashable mapping (a jit static)."""

    def __init__(self, config: Dict):
        super().__init__((k, config[k]) for k in SIZES)

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _pad(a: np.ndarray, bucket: int, fill) -> np.ndarray:
    out = np.full(-(-len(a) // bucket) * bucket, fill, a.dtype)
    out[:len(a)] = a
    return out


def logits(params, spec: Dict, tokens: Sequence[int], rows: Sequence[int],
           *, low: bool = False, q_block: int = 512,
           vocab_block: int = 32768, seq_bucket: int = 1024,
           row_bucket: int = 256) -> np.ndarray:
    """Float32 next-token logits of one sequence at positions ``rows``:
    a host array (len(rows), vocab).  Weights are widened one layer at a
    time, so the model fits beside its bf16 copy.  The sequence is padded
    at its end to a multiple of ``seq_bucket`` (causal attention keeps
    the padding out of every real row) and the rows to a multiple of
    ``row_bucket``, so that a few compiled shapes serve every request."""
    spec = Spec(spec)
    n = len(rows)
    tokens = _pad(np.asarray(tokens, np.int32), seq_bucket, 0)
    rows = np.asarray(rows, np.int32)
    rows = _pad(rows, row_bucket, rows[-1])
    emb = params["embed"]["embedding"]
    x = emb[jnp.asarray(tokens)].astype(F32)[None]
    for i in range(spec["num_hidden_layers"]):
        p_i = jax.tree.map(lambda t: t[i], params["layers"])
        x = _layer_jit(p_i, x, spec=spec, q_block=q_block, low=low,
                       train=False)
    y = _rms(x[0, jnp.asarray(rows)],
             params["final_norm"]["scale"].astype(F32), spec["rms_norm_eps"])
    table = params["embed"]["unembed"]
    out = []
    for lo in range(0, table.shape[1], vocab_block):
        out.append(np.asarray(_unembed(y, table[:, lo:lo + vocab_block],
                                       low)))
    return np.concatenate(out, -1)[:n]


@functools.partial(jax.jit, static_argnames=("low",))
def _unembed(y, table, low):
    return _mm("sd,dv->sv", y, table.astype(F32), low)


# ---------------------------------------------------------------------------
# training
def loss(params, tokens, labels, spec, low=False, q_block=512):
    """Mean cross-entropy + Z_LOSS x mean lse^2 over labels >= 0."""
    x = params["embed"]["embedding"].astype(F32)[tokens]
    layer = jax.checkpoint(functools.partial(
        _layer, spec=spec, q_block=q_block, low=low, train=True))
    # a scan over the stacked layers writes each layer's gradient into
    # the stacked gradient in place (a loop of slices would add a copy)
    x, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), x,
                        params["layers"])
    y = _rms(x, params["final_norm"]["scale"].astype(F32),
             spec["rms_norm_eps"])
    lg = _split(_mm("bsd,dv->bsv", y, params["embed"]["unembed"].astype(F32),
                    low), 2)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    valid = (labels >= 0).astype(F32)
    n = jnp.maximum(valid.sum(), 1.0)
    return (((lse - gold) * valid).sum() + Z_LOSS * (lse * lse * valid).sum()
            ) / n


@functools.partial(jax.jit, static_argnames=("spec", "low"))
def _value_and_grad(params, tokens, labels, spec, low):
    return jax.value_and_grad(loss)(params, tokens, labels, spec, low)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, step, opt):
    """One AdamW step (constant lr); returns (params, m, v, clipped grads'
    per-leaf norms)."""
    lr, b1, b2, eps, wd, clip = opt
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    t = step.astype(F32)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, g, m_, v_):
        g = g * scale
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        delta = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p
        return p - lr * delta, m_, v_

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,          # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    gn = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)) * scale, grads)
    return pick(0), pick(1), pick(2), gn


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(F32)))), tree)


@jax.jit
def gap_norms(a, b):
    """Per-leaf norm of a - b, in one program that holds no difference."""
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))), a, b)


def train_steps(params0, spec: Dict, batches, opt, *, low: bool = False):
    """Run len(batches) AdamW steps from ``params0`` (float32, consumed).

    Returns (losses, per-leaf norms of the first step's clipped
    gradient, the final params)."""
    spec = Spec(spec)
    p = params0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, gnorm1 = [], None
    mesh = getattr(jax.tree.leaves(p)[0].sharding, "mesh", None)
    with jax.default_matmul_precision("highest"), (
            jax.set_mesh(mesh) if mesh is not None
            else contextlib.nullcontext()):
        for i, b in enumerate(batches):
            val, g = _value_and_grad(p, jnp.asarray(b["tokens"]),
                                     jnp.asarray(b["labels"]), spec, low)
            p, m, v, gn = _adamw(p, g, m, v, jnp.asarray(i + 1), opt)
            del g
            losses.append(float(val))
            if gnorm1 is None:
                gnorm1 = jax.tree.map(float, gn)
    return losses, gnorm1, p
