"""The model as the benchmark knows it, from the configuration file alone:
its sizes, random weights made from the seed, and the plain float32
reference forward pass that decides ``correct``.

Nothing here imports the program.  The weights are laid out as the
program's parameter tree expects them (``run.py`` checks the layout
against the program's own ``init_params`` shapes before serving), and
the reference reads the same tree.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

INIT_SCALE = 0.02         # matrices ~ N(0, 0.02^2)
NORM_SCALE_SPREAD = 0.1   # norm scales ~ 1 + N(0, 0.1^2)
FP8_MAX = 448.0           # largest float8_e4m3fn


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

    @staticmethod
    def of(model: dict, override: dict | None = None) -> "Dims":
        m = dict(model, **(override or {}))
        heads = m["num_attention_heads"]
        return Dims(
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
    shapes = weight_shapes(dm)
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


def _block(dm: Dims, quant, x, p):
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, f32(p["ln1"]["scale"]), dm.eps)
    a = p["attn"]
    q = _mm(h, f32(a["wq"]), quant).reshape(S, dm.heads, dm.head_dim)
    k = _mm(h, f32(a["wk"]), quant).reshape(S, dm.kv_heads, dm.head_dim)
    v = _mm(h, f32(a["wv"]), quant).reshape(S, dm.kv_heads, dm.head_dim)
    if dm.qk_norm:
        q = _rms(q, f32(a["q_norm"]["scale"]), dm.eps)
        k = _rms(k, f32(a["k_norm"]["scale"]), dm.eps)
    q, k = _rope(q, pos, dm.rope_theta), _rope(k, pos, dm.rope_theta)
    rep = dm.heads // dm.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / dm.head_dim ** 0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(S, -1), f32(a["wo"]), quant)
    h = _rms(x, f32(p["ln2"]["scale"]), dm.eps)
    m = p["mlp"]
    g = _mm(h, f32(m["w_gate"]), quant)
    u = _mm(h, f32(m["w_up"]), quant)
    return x + _mm(jax.nn.silu(g) * u, f32(m["w_down"]), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def forward(w, tokens, *, dm: Dims, quant: str | None = None):
    """Logits (S, vocab) float32 of one sequence ``tokens`` (S,), every
    position.  Layer by layer (a scan), each layer's bf16 weights widened
    to float32 only while it runs."""
    x = jnp.asarray(w["embed"], jnp.float32)[tokens]
    x, _ = jax.lax.scan(lambda x, p: (_block(dm, quant, x, p), None),
                        x, w["runs"][0])
    x = _rms(x, jnp.asarray(w["final_norm"]["scale"], jnp.float32), dm.eps)
    table = w["embed"].T if dm.tied else w["unembed"]
    logits = _mm(x, jnp.asarray(table, jnp.float32), quant)
    return logits[:, : dm.vocab]
