"""Plain reference of Granite 4.0-H (GraniteMoeHybrid without experts), its
weights and the work a decode step requires.

The architecture, from the published description (the Hugging Face
``granitemoehybrid`` model and its config): x = embed(tokens) *
``embedding_multiplier``; per layer x += ``residual_multiplier`` *
mixer(rmsnorm(x)) and x += ``residual_multiplier`` * mlp(rmsnorm(x)); a
final rmsnorm, and logits = head(x) / ``logits_scaling`` with the head tied
to the embedding.  ``layer_types`` names each layer's mixer:

- ``attention``: causal grouped-query attention (query head h reads
  key/value head h // (H / KV)) with no position encoding (NoPE) and the
  scores scaled by ``attention_multiplier``;
- ``mamba``: Mamba-2.  in_proj gives z, xBC and dt; a depthwise causal conv
  of width ``mamba_d_conv`` with bias, then silu, over xBC, which splits into
  x, B and C; per head dt = softplus(dt + dt_bias), A = -exp(A_log), and
  the recurrence s_t = exp(dt A) s_{t-1} + dt x_t B_t^T, y_t = s_t C_t + D
  x_t, run here as a plain scan over time in float32 (head h reads group
  h // (heads / ``mamba_n_groups``)); then the gated norm rmsnorm(y *
  silu(z)) over all inner channels (``norm_before_gate=False``); out_proj.

The MLP is SwiGLU of width ``shared_intermediate_size``: down(silu(gate(x)) *
up(x)), where gate is the first half of the published ``input_linear``.

Departures from the published description, each also the program's:

- The weights are random, from the seed: ``init_params``.  Matrices are
  normal with std 1/sqrt(fan in), the residual branches' outputs scaled by a
  further 1/sqrt(2L).  The embedding has std 0.02 * residual_multiplier /
  embedding_multiplier, so that it weighs in the residual stream as a 0.02
  embedding does in an unscaled decoder: at 0.02, scaled by 12 against
  branches scaled by 0.22, it would fill the last hidden state, and the
  tied head would make every token predict itself.  Norm scales are 1 + 0.05
  N(0, 1); the conv weight and bias have the std of PyTorch's default init
  for a depthwise conv of width 4 (uniform in +-1/2: 1/sqrt(12)).  The
  recurrence decays as a trained Mamba-2's does at its init: A_log =
  log(U[1, 16]), dt_bias = softplus^-1(dt) with dt log-uniform in [0.001,
  0.1], D = 1 (the Mamba-2 defaults).
- The tied head is held as a second, equal matrix, as the program holds it.
- A configuration cut to fewer layers (the CPU tests') keeps the first
  ``num_hidden_layers`` entries of ``layer_types``, and its Mamba-2 heads are
  ``mamba_expand * hidden_size / mamba_d_head`` (64 at the published size,
  as ``mamba_n_heads``).

This module imports nothing of the program.  It computes in float32 with
every matrix product at ``HIGHEST`` precision; ``fp8=True`` gives the
control, the same arithmetic with both operands of every matrix product
rounded to float8 e4m3's three mantissa bits (the step below the bf16 the
configuration states).  The weights are the bf16 (or f32) values
``init_params`` makes, read as float32.
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
    kinds: Tuple[str, ...]
    d: int
    H: int
    KV: int
    hd: int
    f: int
    V: int
    d_in: int          # Mamba-2 inner width
    mh: int            # Mamba-2 heads
    mhd: int           # Mamba-2 head size
    N: int             # state size
    G: int             # B/C groups
    K: int             # conv width
    eps: float
    attn_scale: float
    m_emb: float
    m_res: float
    logits_scaling: float
    dtype: str

    @property
    def L(self) -> int:
        return len(self.kinds)

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def conv_dim(self) -> int:
        return self.d_in + 2 * self.G * self.N


def dims(cfg: Dict[str, Any]) -> Dims:
    # the layout followed here, which the program's Mamba-2 (one B/C group,
    # a conv bias, no projection bias) shares
    if cfg["position_embedding_type"] != "nope" or cfg["num_local_experts"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or cfg["attention_bias"] or not cfg["tie_word_embeddings"] \
            or cfg["mamba_n_groups"] != 1:
        raise ValueError(f"{cfg['name']}: not the Granite 4.0-H layout this "
                         "reference follows")
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    return Dims(
        kinds=tuple(cfg["layer_types"][:L]), d=d,
        H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], f=cfg["shared_intermediate_size"],
        V=cfg["vocab_size"], d_in=d_in, mh=d_in // cfg["mamba_d_head"],
        mhd=cfg["mamba_d_head"], N=cfg["mamba_d_state"],
        G=cfg["mamba_n_groups"], K=cfg["mamba_d_conv"],
        eps=float(cfg["rms_norm_eps"]),
        attn_scale=float(cfg["attention_multiplier"]),
        m_emb=float(cfg["embedding_multiplier"]),
        m_res=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]), dtype=cfg["torch_dtype"])


def program_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as keyword arguments of the repo's ModelConfig."""
    D = dims(cfg)
    if D.K != 4:
        raise ValueError(f"{cfg['name']}: the program's conv is 4 wide")
    return dict(name=cfg["name"], family="hybrid", n_layers=D.L, d_model=D.d,
                n_heads=D.H, n_kv_heads=D.KV, head_dim=D.hd, d_ff=D.f,
                vocab=D.V, rope="none", attn_scale=D.attn_scale,
                ssm_state=D.N, ssm_head_dim=D.mhd,
                expand=cfg["mamba_expand"],
                layer_types=D.kinds, embedding_multiplier=D.m_emb,
                residual_multiplier=D.m_res, logits_scaling=D.logits_scaling,
                norm_eps=D.eps, dtype=D.dtype, source=cfg["source"])


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

NORM, A_LOG, DT_BIAS, ONE = "norm", "A_log", "dt_bias", "one"


def _shapes(D: Dims) -> Dict[str, Tuple[Tuple[int, ...], str, Any]]:
    """leaf path -> (shape, dtype, init): a std for a normal matrix, or one
    of NORM, A_LOG, DT_BIAS, ONE."""
    d, f, dt, out = D.d, D.f, D.dtype, 1.0 / math.sqrt(2 * D.L)
    conv = 1.0 / math.sqrt(12.0)
    s: Dict[str, Tuple[Tuple[int, ...], str, Any]] = {
        "embedding/embed": ((D.V, d), dt, 0.02 * D.m_res / D.m_emb),
        "ln_final/scale": ((d,), "float32", NORM),
    }
    mixers = {
        "mamba": {
            "w_in": ((d, D.d_in + D.conv_dim + D.mh), dt, d ** -0.5),
            "conv_w": ((D.K, D.conv_dim), dt, conv),
            "conv_b": ((D.conv_dim,), dt, conv),
            "A_log": ((D.mh,), "float32", A_LOG),
            "dt_bias": ((D.mh,), "float32", DT_BIAS),
            "D": ((D.mh,), "float32", ONE),
            "ln_out/scale": ((D.d_in,), "float32", NORM),
            "w_out": ((D.d_in, d), dt, D.d_in ** -0.5 * out),
        },
        "attention": {
            "wq": ((d, D.H, D.hd), dt, d ** -0.5),
            "wk": ((d, D.KV, D.hd), dt, d ** -0.5),
            "wv": ((d, D.KV, D.hd), dt, d ** -0.5),
            "wo": ((D.H, D.hd, d), dt, (D.H * D.hd) ** -0.5 * out),
        },
    }
    for kind, mixer in mixers.items():
        n = D.count(kind)
        if not n:
            continue
        layer = {"ln_mixer/scale": ((d,), "float32", NORM),
                 "ln_mlp/scale": ((d,), "float32", NORM),
                 "mlp/w_gate": ((d, f), dt, d ** -0.5),
                 "mlp/w_up": ((d, f), dt, d ** -0.5),
                 "mlp/w_down": ((f, d), dt, f ** -0.5 * out)}
        layer.update({f"mixer/{k}": v for k, v in mixer.items()})
        for k, (shape, ldt, init) in layer.items():
            s[f"layers/{kind}/{k}"] = ((n,) + shape, ldt, init)
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


def _draw(key, shape, init):
    if init == NORM:
        return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    if init == ONE:
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if init == A_LOG:                            # A in [1, 16]
        return jnp.log(1.0 + 15.0 * u)
    if init == DT_BIAS:                          # dt log-uniform in [1e-3, 0.1]
        dt = jnp.exp(math.log(1e-3) + u * (math.log(0.1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)
    return jax.random.normal(key, shape, jnp.float32) * init


@partial(jax.jit, static_argnums=0)
def _make(D: Dims, seed: jnp.ndarray) -> Dict[str, Any]:
    key = jax.random.key(seed)
    flat = {}
    for i, (path, (shape, dt, init)) in enumerate(sorted(_shapes(D).items())):
        flat[path] = _draw(jax.random.fold_in(key, i), shape, init).astype(dt)
    flat["embedding/unembed"] = flat["embedding/embed"].T
    return _nest(flat)


def init_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Seeded weights in the layout of the repo's layer-list stack (each kind
    of layer stacked on a leading axis), made on the device in one call."""
    return _make(dims(cfg), jnp.uint32(seed))


def leaf_paths(tree) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# --------------------------------------------------------------------------- #
# required work
# --------------------------------------------------------------------------- #

def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Weights that take part in matrix products, per token: the Mamba-2
    and attention mixers, the MLPs, and the output head (the embedding is a
    lookup)."""
    D = dims(cfg)
    mamba = D.d * (D.d_in + D.conv_dim + D.mh) + D.d_in * D.d
    attn = 2 * D.d * D.H * D.hd + 2 * D.d * D.KV * D.hd
    return {"mamba": D.count("mamba") * mamba,
            "attention": D.count("attention") * attn,
            "mlp": D.L * 3 * D.d * D.f, "head": D.d * D.V}


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the repo's model holds (the tied head held twice)."""
    D = dims(cfg)
    return sum(int(np.prod(s)) for s, _, _ in _shapes(D).values()) \
        + D.d * D.V


def _state_bytes(D: Dims) -> Tuple[int, int]:
    """Bytes of one row's Mamba-2 SSM state (float32) and conv state (the
    activations' dtype), over all Mamba-2 layers."""
    w = 2 if D.dtype in ("bfloat16", "float16") else 4
    n = D.count("mamba")
    return (n * D.mh * D.mhd * D.N * 4, n * (D.K - 1) * D.conv_dim * w)


def decode_flops(cfg: Dict[str, Any], positions: Sequence[int]) -> float:
    """FLOPs of one decode step whose active rows write positions
    ``positions`` (0-based): 2 per matrix weight per row; attention over the
    p + 1 positions each row holds in each attention layer; and per Mamba-2
    layer and row, 3 per state element to update it (two products, one sum)
    and 2 to read it out.  Empty rows are not counted."""
    D = dims(cfg)
    per_row = 2.0 * sum(matmul_params(cfg).values()) \
        + 5.0 * D.count("mamba") * D.mh * D.mhd * D.N
    held = float(sum(p + 1 for p in positions))
    return per_row * len(positions) \
        + 4.0 * D.count("attention") * D.H * D.hd * held


def decode_bytes(cfg: Dict[str, Any], positions: Sequence[int]) -> float:
    """Bytes one decode step must move: every weight once (the head once, the
    embedding only for the rows looked up); for each active row its Mamba-2
    SSM and conv state, read and written; and the keys and values each
    active row holds in the attention layers, the new ones included."""
    D = dims(cfg)
    w = 2 if D.dtype in ("bfloat16", "float16") else 4
    weights = sum(matmul_params(cfg).values()) * w
    small = D.count("mamba") * (D.K * D.conv_dim * w + D.conv_dim * w
                                + 3 * D.mh * 4 + D.d_in * 4) \
        + D.L * 2 * D.d * 4 + D.d * 4 + len(positions) * D.d * w
    ssm, conv = _state_bytes(D)
    kv_per_pos = 2 * D.count("attention") * D.KV * D.hd * w
    return weights + small + 2.0 * (ssm + conv) * len(positions) \
        + kv_per_pos * float(sum(p + 1 for p in positions))


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

def _fp8(x):
    """Round to float8 e4m3's three explicit mantissa bits.  The exponent is
    left free, as a per-tensor scale would keep it in range."""
    m, e = jnp.frexp(x)                          # x = m 2^e, 0.5 <= |m| < 1
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(spec: str, x, w, fp8: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _attention(D: Dims, fp8: bool, a, h):
    B, S, _ = h.shape
    q = _mm("bsd,dhk->bshk", h, a["wq"], fp8)
    k = _mm("bsd,dhk->bshk", h, a["wk"], fp8)
    v = _mm("bsd,dhk->bshk", h, a["wv"], fp8)
    g = D.H // D.KV
    k = jnp.repeat(k, g, axis=2)                 # head h reads kv head h // g
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) * D.attn_scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v, precision=HI)
    wo = a["wo"].reshape(D.H * D.hd, D.d)
    return _mm("bsk,kd->bsd", o.reshape(B, S, D.H * D.hd), wo, fp8)


def _mamba(D: Dims, fp8: bool, m, h):
    B, S, _ = h.shape
    proj = _mm("bsd,de->bse", h, m["w_in"], fp8)
    z = proj[..., :D.d_in]
    xbc = proj[..., D.d_in:D.d_in + D.conv_dim]
    dt = proj[..., D.d_in + D.conv_dim:]
    padded = jnp.pad(xbc, ((0, 0), (D.K - 1, 0), (0, 0)))
    conv = m["conv_b"].astype(jnp.float32) + sum(
        padded[:, i:i + S] * m["conv_w"][i].astype(jnp.float32)
        for i in range(D.K))
    conv = jax.nn.silu(conv)
    x = conv[..., :D.d_in].reshape(B, S, D.mh, D.mhd)
    rep = D.mh // D.G                            # head h reads group h // rep
    Bm = jnp.repeat(conv[..., D.d_in:D.d_in + D.G * D.N]
                    .reshape(B, S, D.G, D.N), rep, axis=2)
    Cm = jnp.repeat(conv[..., D.d_in + D.G * D.N:]
                    .reshape(B, S, D.G, D.N), rep, axis=2)
    dt = jax.nn.softplus(dt + m["dt_bias"])      # [B, S, heads]
    A = -jnp.exp(m["A_log"])

    def step(s, t):
        xt, dtt, Bt, Ct = t                      # [B,h,p], [B,h], [B,h,n] x2
        s = jnp.exp(dtt * A)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :]
        return s, jnp.sum(s * Ct[:, :, None, :], -1)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)
    s0 = jnp.zeros((B, D.mh, D.mhd, D.N), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(map(time_first, (x, dt, Bm, Cm))))
    y = jnp.moveaxis(y, 0, 1) + m["D"][:, None] * x
    y = y.reshape(B, S, D.d_in) * jax.nn.silu(z)
    y = _rms(y, m["ln_out"]["scale"], D.eps)
    return _mm("bse,ed->bsd", y, m["w_out"], fp8)


def _layer(D: Dims, fp8: bool, kind: str, x, lp):
    h = _rms(x, lp["ln_mixer"]["scale"], D.eps)
    mix = _mamba if kind == "mamba" else _attention
    x = x + D.m_res * mix(D, fp8, lp["mixer"], h)
    m = lp["mlp"]
    h = _rms(x, lp["ln_mlp"]["scale"], D.eps)
    gate = _mm("bsd,df->bsf", h, m["w_gate"], fp8)
    up = _mm("bsd,df->bsf", h, m["w_up"], fp8)
    return x + D.m_res * _mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                             m["w_down"], fp8)


def _runs(kinds: Tuple[str, ...]):
    """The shortest period the list repeats, as runs of one kind:
    (period length, [(kind, first index of that kind in the period,
    layers)])."""
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs, seen = [], {}
    for kind in kinds[:p]:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return p, runs, seen


def logits(D: Dims, fp8: bool, params, tokens):
    """[B, S] tokens -> [B, S, V] float32 logits, the layers in the order of
    ``D.kinds``: a scan over the periods of the list, and in each period a
    scan over each run of consecutive layers of one kind (so that the
    program compiles each kind of layer a few times, not once a layer)."""
    x = jnp.take(params["embedding"]["embed"], tokens, axis=0)
    x = x.astype(jnp.float32) * D.m_emb
    p, runs, per_period = _runs(D.kinds)

    def period(x, i):
        for kind, first, n in runs:
            stack = jax.tree_util.tree_map(
                lambda t: jax.lax.dynamic_slice_in_dim(
                    t, i * per_period[kind] + first, n),
                params["layers"][kind])
            x, _ = jax.lax.scan(
                lambda x, lp: (_layer(D, fp8, kind, x, lp), None), x, stack)
        return x, None

    x, _ = jax.lax.scan(period, x, jnp.arange(D.L // p))
    x = _rms(x, params["ln_final"]["scale"], D.eps)
    return _mm("bsd,dv->bsv", x, params["embedding"]["unembed"], fp8) \
        / D.logits_scaling


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
    nothing before it) and run alone."""
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
