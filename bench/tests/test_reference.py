"""The reference-module hook (``spec.reference``).  The dense default,
``model.py``, makes the weights and logits it made when it was the only
model the harness knew; and a second module, ``qkv_bias.py``, is served
and checked through its configuration file's ``"reference"`` key alone,
at the rehearsal width on the CPU."""
import dataclasses
import hashlib
import os

import jax
import numpy as np
import pytest

import run as bench_run
import spec
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2654435769

# sha256 of the weights (leaves in tree order) and of the float32 logits,
# plain and fp8, of 96 fixed tokens, at each file's cpu_rehearsal sizes
# from SEED.  The logits' bits hold under XLA's default CPU flags.
PINNED = {
    "qwen3-4b": (
        "c05f944076706f5dedb3da33084e05bb6e55e7efe743bbccc90daa85e7b85d7f",
        "61d18d0173ba47fdcae32172a06eb81063476272ba0776dd972c007b62d6779f",
        "70972f9c423b7711ff04ec5bc68a2b6cb893dc8dee151f74b643c743769b27f2"),
    "internlm2-1.8b": (
        "b8218419fd3335fb5e08deb613f38d2629d6946c6348528401d374b310e54b71",
        "a24e87f98beb4c7c3ee2d21bd188e1d921a053cc4518fdc156693f9a200cd2d5",
        "5787ce021f186d285cdec9e6116b2ccd7ab66f6ea7badb2086ae0195f342c1c2"),
}


def sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def rehearsal_dims(conf):
    small = conf["cpu_rehearsal"]
    return spec.reference(conf).Dims.of(
        conf["model"], {k: v for k, v in small.items() if k in conf["model"]})


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_default_weights_and_logits_unmoved(name):
    conf = spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")
    ref = spec.reference(conf)
    assert "reference" not in conf and ref.__name__ == "model"
    dm = rehearsal_dims(conf)
    w = ref.make_weights(dm, bench_run.seed_key(SEED))
    tokens = (np.arange(96, dtype=np.int32) * 37 + 11) % dm.vocab
    got = (sha(jax.tree_util.tree_leaves(w)),
           sha([ref.forward(w, tokens, dm=dm)]),
           sha([ref.forward(w, tokens, dm=dm, quant="fp8")]))
    assert got == PINNED[name]


# --- the second module -----------------------------------------------------

FIXTURE = os.path.join(DATA, "qwen1.5-4b.json")


def test_second_module_counts_match_program():
    """Its weights have the program's qwen1.5-4b tree at full width, and
    ``work.py`` answers with its counts, biases included."""
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models.transformer import init_params

    conf = spec.load_json(FIXTURE)
    ref = spec.reference(conf)
    dm = ref.Dims.of(conf["model"])
    assert spec.reference_of(dm) is ref and ref.__name__ == "tests.qkv_bias"
    cfg = get_config(conf["arch"])
    want = jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: ref.make_weights(dm, jax.random.key(0)))
    tu = jax.tree_util
    assert tu.tree_structure(got) == tu.tree_structure(want)
    assert tu.tree_leaves(got) == tu.tree_leaves(want)
    n = sum(x.size for x in tu.tree_leaves(want))
    assert work.param_count(dm) == n
    dense = spec.reference({}).Dims.of(conf["model"])
    bias = dm.layers * 3 * dm.heads * dm.head_dim
    assert work.param_count(dense) == n - bias
    assert (work.decode_step_bytes(dm, [7, 9])
            == work.decode_step_bytes(dense, [7, 9]) + 2 * bias)


def fixture_cell():
    """The batch_sampled mix served to the fixture configuration."""
    cell = spec.load_cell("internlm2-1.8b.batch_sampled")
    return dataclasses.replace(cell, name="qwen1.5-4b.batch_sampled",
                               config=spec.load_json(FIXTURE))


def serve_fixture(monkeypatch, capsys, seed):
    cell = fixture_cell()
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    jax.clear_caches()
    code, result = bench_run.execute(bench_run.parse(
        ["--workload", cell.name, "--seed", str(seed), "--seconds", "8",
         "--trace", "0", "--reduced"]))
    assert code == 3 and result is not None
    assert "rehearsal passed every phase" in capsys.readouterr().err
    return result


def test_second_module_rehearsal_is_correct(monkeypatch, capsys):
    result = serve_fixture(monkeypatch, capsys, 21)
    checks = result["checks"]
    assert "greedy_gap" in checks and "kept_gap" in checks, checks
    assert result["correct"], checks


def test_second_module_kv_bias_dropped_is_not_correct(monkeypatch, capsys):
    """The program's k and v projections leave out their bias.  Over
    seeds 21-28 the sound program read at most 0.0097 on either gap and
    this fault at least 0.67 (greedy) and 0.47 (kept); the fixture's
    limits, 0.03, lie between."""
    from repro.models import attention

    orig = attention._project_kv

    def unbiased(p, cfg, x):
        return orig(p, dataclasses.replace(cfg, qkv_bias=False), x)

    monkeypatch.setattr(attention, "_project_kv", unbiased)
    result = serve_fixture(monkeypatch, capsys, 22)
    assert not result["correct"], result["checks"]
