"""Plain reference of a dense decoder (Llama / Qwen2 layout), its weights and
its required work.

The architecture, from the published descriptions: token embedding; per
layer x += attn(rmsnorm(x)) and x += mlp(rmsnorm(x)); a final rmsnorm and
the output head.  Attention is causal, grouped-query (query head h reads
key/value head h // (H / KV)), with rotary embeddings on q and k (the
rotate-half form: frequencies theta^(-2i/hd) over the two halves of a head)
and, where ``attention_bias`` is set, biases on q, k and v.  The MLP is
SwiGLU: down(silu(gate(x)) * up(x)).

This module imports nothing of the program.  It computes in float32 with
every matrix product at ``HIGHEST`` precision; ``fp8=True`` gives the
control, the same arithmetic with both operands of every matrix product
rounded to float8 e4m3's three mantissa bits (the step below the bf16 the
configurations state).  The
weights are the bf16 (or f32) values ``init_params`` makes, read as float32,
and a training step stores its update back in each leaf's own dtype, as the
configuration's bf16 weights and float32 optimizer moments state.

It also holds what the benchmark needs to know of the architecture:
``program_kwargs`` (the configuration in the repo's ``ModelConfig`` terms),
``init_params`` (seeded weights in the repo's parameter layout) and the
FLOP and byte counts of the work a step requires.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    L: int
    d: int
    H: int
    KV: int
    hd: int
    f: int
    V: int
    eps: float
    theta: float
    bias: bool
    tied: bool
    dtype: str


def dims(cfg: Dict[str, Any]) -> Dims:
    return Dims(
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], f=cfg["intermediate_size"], V=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        bias=bool(cfg["attention_bias"]),
        tied=bool(cfg["tie_word_embeddings"]), dtype=cfg["torch_dtype"])


def program_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as keyword arguments of the repo's ModelConfig."""
    D = dims(cfg)
    return dict(name=cfg["name"], family="dense", n_layers=D.L,
                d_model=D.d, n_heads=D.H, n_kv_heads=D.KV, head_dim=D.hd,
                d_ff=D.f, vocab=D.V, qkv_bias=D.bias, rope="rope",
                rope_theta=D.theta, norm_eps=D.eps, dtype=D.dtype,
                source=cfg["source"])


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

def _shapes(D: Dims) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """leaf path -> (shape, dtype, std).  std 0 marks a norm scale (1 +
    0.05 N(0, 1)).  Residual-branch outputs are scaled by 1/sqrt(2L) so that
    the stack neither explodes nor lets the embedding dominate the logits."""
    L, d, H, KV, hd, f, V = D.L, D.d, D.H, D.KV, D.hd, D.f, D.V
    dt, out = D.dtype, 1.0 / math.sqrt(2 * L)
    s = {
        "embedding/embed": ((V, d), dt, 0.02),
        "layers/ln_attn/scale": ((L, d), "float32", 0.0),
        "layers/attn/wq": ((L, d, H, hd), dt, d ** -0.5),
        "layers/attn/wk": ((L, d, KV, hd), dt, d ** -0.5),
        "layers/attn/wv": ((L, d, KV, hd), dt, d ** -0.5),
        "layers/attn/wo": ((L, H, hd, d), dt, (H * hd) ** -0.5 * out),
        "layers/ln_mlp/scale": ((L, d), "float32", 0.0),
        "layers/mlp/w_gate": ((L, d, f), dt, d ** -0.5),
        "layers/mlp/w_up": ((L, d, f), dt, d ** -0.5),
        "layers/mlp/w_down": ((L, f, d), dt, f ** -0.5 * out),
        "ln_final/scale": ((d,), "float32", 0.0),
    }
    if D.bias:
        s["layers/attn/bq"] = ((L, H, hd), dt, 0.1)
        s["layers/attn/bk"] = ((L, KV, hd), dt, 0.1)
        s["layers/attn/bv"] = ((L, KV, hd), dt, 0.1)
    if not D.tied:
        s["embedding/unembed"] = ((d, V), dt, 0.02)
    return s


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


@partial(jax.jit, static_argnums=0)
def _make(D: Dims, seed: jnp.ndarray) -> Dict[str, Any]:
    key = jax.random.key(seed)
    flat = {}
    for i, (path, (shape, dt, std)) in enumerate(sorted(_shapes(D).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = 1.0 + 0.05 * z if std == 0.0 else z * std
        flat[path] = w.astype(dt)
    if D.tied:
        flat["embedding/unembed"] = flat["embedding/embed"].T
    return _nest(flat)


def init_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Seeded weights in the layout of the repo's dense decoder (layers
    stacked on a leading axis), made on the device in one jitted call."""
    return _make(dims(cfg), jnp.uint32(seed))


def leaf_paths(tree) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# --------------------------------------------------------------------------- #
# required work
# --------------------------------------------------------------------------- #

def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Weights that take part in matrix products, per token: the layer
    stack and the output head (the embedding is a lookup)."""
    D = dims(cfg)
    layer = (D.d * D.H * D.hd + 2 * D.d * D.KV * D.hd + D.H * D.hd * D.d
             + 3 * D.d * D.f)
    return {"layers": D.L * layer, "head": D.d * D.V}


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the repo's model holds (untied head included)."""
    return sum(int(np.prod(s)) for s, _, _ in _shapes(dims(cfg)).values()) \
        + (dims(cfg).d * dims(cfg).V if dims(cfg).tied else 0)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward FLOPs a token of a causal sequence of ``seq``
    requires: 6 per matrix weight, and 6 * H * hd * (seq + 1) per layer for
    attention (QK^T and PV over the causal half, 2 FLOPs a product, times 3
    for forward and backward).  Recomputation is not counted."""
    D, mp = dims(cfg), matmul_params(cfg)
    attn = 6 * D.L * D.H * D.hd * (seq + 1)
    return 6.0 * (mp["layers"] + mp["head"]) + attn


def decode_flops(cfg: Dict[str, Any], positions: Sequence[int]) -> float:
    """FLOPs of one decode step whose active rows write positions
    ``positions`` (0-based): 2 per matrix weight per row, and attention over
    the p + 1 positions each row holds.  Empty rows are not counted."""
    D, mp = dims(cfg), matmul_params(cfg)
    per_row = 2.0 * (mp["layers"] + mp["head"])
    held = float(sum(p + 1 for p in positions))
    return per_row * len(positions) + 4.0 * D.L * D.H * D.hd * held


def decode_bytes(cfg: Dict[str, Any], positions: Sequence[int]) -> float:
    """Bytes one decode step must move: every weight once (the head once, the
    embedding only for the rows looked up), the keys and values each active
    row already holds, and the new key and value it writes."""
    D, mp = dims(cfg), matmul_params(cfg)
    w = 2 if D.dtype in ("bfloat16", "float16") else 4
    weights = (mp["layers"] + mp["head"]) * w
    small = D.L * (2 * D.d * 4 + (D.H + 2 * D.KV) * D.hd * w * D.bias) \
        + D.d * 4 + len(positions) * D.d * w
    kv_per_pos = 2 * D.L * D.KV * D.hd * w
    return weights + small + kv_per_pos * float(sum(p + 1 for p in positions))


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

def _fp8(x):
    """Round to float8 e4m3's three explicit mantissa bits, straight through
    for the gradient.  The exponent is left free, as a per-tensor scale
    would keep it in range, so nothing overflows or goes subnormal."""
    m, e = jnp.frexp(x)                          # x = m 2^e, 0.5 <= |m| < 1
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, x, w, fp8: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: [B, S, heads, hd] at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs     # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(D: Dims, fp8: bool, x, lp):
    B, S, _ = x.shape
    a = lp["attn"]
    h = _rms(x, lp["ln_attn"]["scale"], D.eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], fp8)
    k = _mm("bsd,dhk->bshk", h, a["wk"], fp8)
    v = _mm("bsd,dhk->bshk", h, a["wv"], fp8)
    if D.bias:
        q, k, v = (q + a["bq"].astype(jnp.float32),
                   k + a["bk"].astype(jnp.float32),
                   v + a["bv"].astype(jnp.float32))
    q, k = _rope(q, D.theta), _rope(k, D.theta)
    g = D.H // D.KV
    k = jnp.repeat(k, g, axis=2)                 # head h reads kv head h // g
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / math.sqrt(D.hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v, precision=HI)
    o = o.reshape(B, S, D.H * D.hd)
    wo = a["wo"].reshape(D.H * D.hd, D.d)
    x = x + _mm("bsk,kd->bsd", o, wo, fp8)
    m = lp["mlp"]
    h = _rms(x, lp["ln_mlp"]["scale"], D.eps)
    gate = _mm("bsd,df->bsf", h, m["w_gate"], fp8)
    up = _mm("bsd,df->bsf", h, m["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w_down"],
                   fp8)


def logits(D: Dims, fp8: bool, params, tokens):
    """[B, S] tokens -> [B, S, V] float32 logits."""
    x = jnp.take(params["embedding"]["embed"], tokens, axis=0)
    x = x.astype(jnp.float32)

    def body(x, lp):
        return jax.checkpoint(partial(_layer, D, fp8))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["ln_final"]["scale"], D.eps)
    return _mm("bsd,dv->bsv", x, params["embedding"]["unembed"], fp8)


# --------------------------------------------------------------------------- #
# serving: the gap of each served token below the reference's best
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnums=(0, 1))
def _gaps(D: Dims, control: bool, params, tokens, targets):
    """Per position of one row: the reference's best logit minus its logit
    of ``targets`` (the served token) and, with ``control``, minus its logit
    of the token the fp8 control puts first."""
    ref = logits(D, False, params, tokens)[0]
    best = jnp.max(ref, -1)
    at = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
    gap = best - at(targets[0])
    if not control:
        return gap, gap
    ctl = logits(D, True, params, tokens)[0]
    return gap, best - at(jnp.argmax(ctl, -1))


def served_gaps(cfg: Dict[str, Any], seed: int,
                served: Sequence[Tuple[List[int], List[int]]], pad_to: int,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """For (prompt, served tokens) pairs: the gap of every served token and,
    with ``control``, of the token the control puts first at the same
    positions.  Each row is padded to ``pad_to`` (causal, so padding changes
    nothing before it) and run alone, so that the reference fits."""
    D = dims(cfg)
    params = init_params(cfg, seed)
    prog, ctl = [], []
    for prompt, toks in served:
        seq = list(prompt) + list(toks[:-1])
        first = len(prompt) - 1                  # predicts toks[0]
        x = np.zeros((1, pad_to), np.int32)
        y = np.zeros((1, pad_to), np.int32)
        x[0, :len(seq)] = seq
        y[0, first:first + len(toks)] = toks
        gp, gc = _gaps(D, control, params, jnp.asarray(x), jnp.asarray(y))
        sl = slice(first, first + len(toks))
        prog.append(np.asarray(gp)[sl])
        ctl.append(np.asarray(gc)[sl])
    return np.concatenate(prog), np.concatenate(ctl)


# --------------------------------------------------------------------------- #
# training: the first steps of AdamW from the seeded weights
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnums=(0, 1))
def _block_grad(D: Dims, fp8: bool, params, tokens, labels):
    def nll_sum(pf):
        lg = logits(D, fp8, pf, tokens)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

    pf = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    return jax.value_and_grad(nll_sum)(pf)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _adamw(params, g, m, v, step, lr, scale, opt):
    b1, b2, eps, wd = opt

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** step)
        vh = v / (1 - b2 ** step)
        pf = p.astype(jnp.float32)
        new = pf - lr * (mh / (jnp.sqrt(vh) + eps) + wd * pf)
        return new.astype(p.dtype), m, v

    out = jax.tree_util.tree_map(upd, params, g, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def _change(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def train_steps(cfg: Dict[str, Any], opt: Dict[str, float], seed: int,
                batches: Sequence[np.ndarray], *, fp8: bool = False,
                block_rows: int = 1) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` AdamW steps from ``init_params(cfg,
    seed)``.  ``batches`` are [B, S] token arrays; the labels are the tokens
    shifted by one, wrapping at the end of a row, as the repo's feeder
    makes them.  The step's loss is the mean next-token NLL over the batch;
    the gradient is clipped to a global norm of ``grad_clip``; the learning
    rate rises linearly from 0 over ``warmup`` steps.

    Returns the loss of each step, the norm of each leaf's first (clipped)
    gradient, and the norm of each leaf's change after the last step."""
    D = dims(cfg)
    params = init_params(cfg, seed)
    p0 = params
    zeros = lambda t: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), t)
    m, v = zeros(params), zeros(params)
    hyper = tuple(float(opt[k]) for k in ("b1", "b2", "eps", "weight_decay"))
    losses, first = [], None
    for i, tokens in enumerate(batches):
        tokens = np.asarray(tokens, np.int32)
        labels = np.roll(tokens, -1, axis=1)
        total, grads = 0.0, None
        for r in range(0, tokens.shape[0], block_rows):
            nll, g = _block_grad(D, fp8, params,
                                 jnp.asarray(tokens[r:r + block_rows]),
                                 jnp.asarray(labels[r:r + block_rows]))
            total += float(nll)
            grads = g if grads is None else _add(grads, g)
        n = tokens.size
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        losses.append(total / n)
        gnorm = float(np.sqrt(sum(float(x) ** 2 for x in _norms(grads))))
        scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        if first is None:
            first = [float(x) * scale for x in _norms(grads)]
        lr = opt["lr_peak"] * i / opt["warmup"] if i < opt["warmup"] \
            else opt["lr_peak"]
        params, m, v = _adamw(params, grads, m, v, jnp.float32(i + 1),
                              jnp.float32(lr), jnp.float32(scale), hyper)
    names = leaf_paths(params)
    return {"losses": losses,
            "grad_norms": dict(zip(names, first)),
            "change_norms": dict(zip(names, map(float, _change(params, p0))))}
