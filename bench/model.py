"""The dense model as the benchmark knows it, from the configuration file
alone: its sizes, random weights made from the seed, the plain float32
reference forward pass that decides ``correct``, the program fields the
file fixes, and the work a step needs.

This is the default reference module: a configuration file with no
``"reference"`` key is served and checked with it (``spec.reference``,
which lists what a reference module provides).  Nothing here imports the
program.  The weights are laid out as the program's parameter tree
expects them (``run.py`` checks the layout against the program's own
``init_params`` shapes before serving), and the reference reads the same
tree.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

INIT_SCALE = 0.02         # matrices ~ N(0, 0.02^2)
NORM_SCALE_SPREAD = 0.1   # norm scales ~ 1 + N(0, 0.1^2)
FP8_MAX = 448.0           # largest float8_e4m3fn
BF16 = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    qk_norm: bool

    @classmethod
    def of(cls, model: dict, override: dict | None = None) -> "Dims":
        m = dict(model, **(override or {}))
        heads = m["num_attention_heads"]
        return cls(
            layers=m["num_hidden_layers"], d=m["hidden_size"], heads=heads,
            kv_heads=m["num_key_value_heads"],
            head_dim=m.get("head_dim") or m["hidden_size"] // heads,
            ff=m["intermediate_size"], vocab=m["vocab_size"],
            rope_theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]),
            tied=bool(m["tie_word_embeddings"]), qk_norm=bool(m["qk_norm"]))

    @property
    def vocab_rows(self) -> int:
        """Rows of the embedding table: the vocab rounded up to 128."""
        return -(-self.vocab // 128) * 128


def program_fields(dm: Dims) -> dict:
    """The program's ``ModelConfig`` fields the configuration fixes:
    ``run.py`` raises where the program's architecture departs from them."""
    return dict(n_layers=dm.layers, d_model=dm.d, n_heads=dm.heads,
                n_kv_heads=dm.kv_heads, head_dim=dm.head_dim, d_ff=dm.ff,
                vocab=dm.vocab, rope_theta=dm.rope_theta, norm_eps=dm.eps,
                tie_embeddings=dm.tied, qk_norm=dm.qk_norm, qkv_bias=False,
                act="swiglu", norm="rmsnorm", family="dense",
                learned_pos=False)


def set_fields(dm: Dims, reduced: bool) -> dict:
    """The fields ``run.py`` sets on the program's registered config: the
    norm epsilon the file states and, in a ``--reduced`` rehearsal, its
    sizes."""
    fields = dict(norm_eps=dm.eps)
    if reduced:
        fields.update(n_layers=dm.layers, d_model=dm.d, n_heads=dm.heads,
                      n_kv_heads=dm.kv_heads, d_head=dm.head_dim, d_ff=dm.ff,
                      vocab=dm.vocab)
    return fields


def _layer_shapes(dm: Dims) -> dict:
    d, hd, L = dm.d, dm.head_dim, dm.layers
    attn = {"wq": (L, d, dm.heads * hd), "wk": (L, d, dm.kv_heads * hd),
            "wv": (L, d, dm.kv_heads * hd), "wo": (L, dm.heads * hd, d)}
    if dm.qk_norm:
        attn["q_norm"] = {"scale": (L, hd)}
        attn["k_norm"] = {"scale": (L, hd)}
    return {"ln1": {"scale": (L, d)}, "attn": attn, "ln2": {"scale": (L, d)},
            "mlp": {"w_gate": (L, d, dm.ff), "w_up": (L, d, dm.ff),
                    "w_down": (L, dm.ff, d)}}


def weight_shapes(dm: Dims) -> dict:
    tree = {"embed": (dm.vocab_rows, dm.d), "runs": [_layer_shapes(dm)],
            "final_norm": {"scale": (dm.d,)}}
    if not dm.tied:
        tree["unembed"] = (dm.d, dm.vocab_rows)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_weights(dm: Dims, key, dtype=jnp.bfloat16):
    """All weights in one jitted call on the device, in ``dtype``."""
    return init_weights(weight_shapes(dm), key, dtype)


def init_weights(shapes: dict, key, dtype):
    """A tree of ``shapes`` drawn from ``key`` in one jitted call: norm
    scales (a ``scale`` leaf) ~ 1 + N(0, 0.1^2), the rest ~ N(0, 0.02^2)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(k, shape, dtype)
            if "scale" in name:
                out.append((1 + NORM_SCALE_SPREAD * z).astype(dtype))
            else:
                out.append((INIT_SCALE * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init)(key)


# ---------------------------------------------------------------------------
# reference forward pass
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3fn with one scale per row (last axis reduced)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x @ w in float32 at full precision; ``quant="fp8"`` rounds both
    operands to fp8 first (the control: one precision step below bf16)."""
    if quant == "fp8":
        x = _fp8(x)
        w = _fp8(w.T).T              # one scale per output column
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, hd); the half-split rotation of the published models."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def project(dm: Dims, quant, h, a):
    """q (S, heads, hd), k and v (S, kv_heads, hd) of the normed input
    ``h`` under the layer's attention weights ``a``."""
    S = h.shape[0]
    q = _mm(h, _f32(a["wq"]), quant).reshape(S, dm.heads, dm.head_dim)
    k = _mm(h, _f32(a["wk"]), quant).reshape(S, dm.kv_heads, dm.head_dim)
    v = _mm(h, _f32(a["wv"]), quant).reshape(S, dm.kv_heads, dm.head_dim)
    return q, k, v


def _block(dm: Dims, quant, project, x, p):
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, _f32(p["ln1"]["scale"]), dm.eps)
    a = p["attn"]
    q, k, v = project(dm, quant, h, a)
    if dm.qk_norm:
        q = _rms(q, _f32(a["q_norm"]["scale"]), dm.eps)
        k = _rms(k, _f32(a["k_norm"]["scale"]), dm.eps)
    q, k = _rope(q, pos, dm.rope_theta), _rope(k, pos, dm.rope_theta)
    rep = dm.heads // dm.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / dm.head_dim ** 0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(S, -1), _f32(a["wo"]), quant)
    h = _rms(x, _f32(p["ln2"]["scale"]), dm.eps)
    m = p["mlp"]
    g = _mm(h, _f32(m["w_gate"]), quant)
    u = _mm(h, _f32(m["w_up"]), quant)
    return x + _mm(jax.nn.silu(g) * u, _f32(m["w_down"]), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def forward(w, tokens, *, dm: Dims, quant: str | None = None):
    """Logits (S, vocab) float32 of one sequence ``tokens`` (S,), every
    position.  Layer by layer (a scan), each layer's bf16 weights widened
    to float32 only while it runs.  ``quant="fp8"`` is the control."""
    return logits(w, tokens, dm, quant, project)


def logits(w, tokens, dm: Dims, quant, project):
    """``forward``'s body, with the attention projections ``project``
    (this module's, or another reference module's around them)."""
    x = jnp.asarray(w["embed"], jnp.float32)[tokens]
    x, _ = jax.lax.scan(
        lambda x, p: (_block(dm, quant, project, x, p), None),
        x, w["runs"][0])
    x = _rms(x, jnp.asarray(w["final_norm"]["scale"], jnp.float32), dm.eps)
    table = w["embed"].T if dm.tied else w["unembed"]
    out = _mm(x, jnp.asarray(table, jnp.float32), quant)
    return out[:, : dm.vocab]


# ---------------------------------------------------------------------------
# work a step needs (``work.py`` answers from here for this module's Dims)
# ---------------------------------------------------------------------------

def layer_matmul_params(dm: Dims) -> int:
    d, hd = dm.d, dm.head_dim
    attn = d * dm.heads * hd * 2 + d * dm.kv_heads * hd * 2
    return attn + 3 * d * dm.ff


def param_count(dm: Dims) -> int:
    """Every weight: matrices, norm scales, embedding and unembedding."""
    norms = 2 * dm.d + (2 * dm.head_dim if dm.qk_norm else 0)
    table = dm.vocab_rows * dm.d
    return (dm.layers * (layer_matmul_params(dm) + norms) + dm.d
            + table * (1 if dm.tied else 2))


def weight_bytes(dm: Dims) -> int:
    return param_count(dm) * BF16


def kv_bytes_per_position(dm: Dims) -> int:
    """K and V of one position over all layers, bf16."""
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * BF16


def _attn_flops(dm: Dims, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` positions."""
    return dm.layers * 4 * dm.heads * dm.head_dim * keys


def decode_token_flops(dm: Dims, pos: int) -> int:
    """One token at position ``pos`` (attending to pos + 1 keys)."""
    return (2 * dm.layers * layer_matmul_params(dm) + 2 * dm.d * dm.vocab
            + _attn_flops(dm, pos + 1))


def prefill_flops(dm: Dims, length: int) -> int:
    """A prompt of ``length`` tokens, causal, logits of the last only."""
    causal_keys = length * (length + 1) // 2
    return (2 * dm.layers * layer_matmul_params(dm) * length
            + 2 * dm.d * dm.vocab + _attn_flops(dm, causal_keys))


def decode_step_bytes(dm: Dims, positions: list[int]) -> int:
    """One decode step over live slots feeding the tokens at
    ``positions``: every weight once (the gathered embedding rows instead
    of the whole table where the unembedding is a table of its own), the
    K/V of the positions before each token, and one K/V row written per
    slot."""
    weights = weight_bytes(dm)
    if not dm.tied:
        weights -= dm.vocab_rows * dm.d * BF16
        weights += len(positions) * dm.d * BF16
    kv = kv_bytes_per_position(dm)
    return weights + sum(p * kv for p in positions) + len(positions) * kv
