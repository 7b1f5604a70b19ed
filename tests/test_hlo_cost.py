"""Collective accounting in launch/hlo_cost.py (DESIGN.md §11).

The tuner's join term is priced from ``analyse_hlo``'s
``collective_detail`` — per-collective-kind execution counts and payload
bytes, loop-trip multiplied.  Two layers:

  * a hand-written HLO module with an all-reduce INSIDE a while loop:
    the detail must report the loop-multiplied count and bytes (the
    whole point of the loop-aware walk — ``cost_analysis()`` would count
    the body once);
  * a real ``shard_map`` psum program lowered under 2 forced host
    devices (subprocess: the forced-device flag must not leak into this
    pytest process): the compiled HLO must yield at least one all-reduce
    with positive payload, and ``tuning.join_term_from_hlo`` must price
    it to a positive join cost.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.hlo_cost import analyse_hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 6-trip while loop whose body all-reduces an f32[8,15] (480 B payload):
# collective_detail must report count=6, bytes=6*480.
LOOPED_ALL_REDUCE_HLO = textwrap.dedent("""
    HloModule tuned_join_test

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
    }

    %cond (p: (s32[], f32[8,15])) -> pred[] {
      %p = (s32[], f32[8,15]) parameter(0)
      %i = s32[] get-tuple-element((s32[], f32[8,15]) %p), index=0
      %trips = s32[] constant(6)
      ROOT %lt = pred[] compare(s32[] %i, s32[] %trips), direction=LT
    }

    %body (p: (s32[], f32[8,15])) -> (s32[], f32[8,15]) {
      %p.1 = (s32[], f32[8,15]) parameter(0)
      %x = f32[8,15] get-tuple-element((s32[], f32[8,15]) %p.1), index=1
      %ar = f32[8,15] all-reduce(f32[8,15] %x), replica_groups={}, to_apply=%add
      %i.1 = s32[] get-tuple-element((s32[], f32[8,15]) %p.1), index=0
      %one = s32[] constant(1)
      %next = s32[] add(s32[] %i.1, s32[] %one)
      ROOT %tup = (s32[], f32[8,15]) tuple(s32[] %next, f32[8,15] %ar)
    }

    ENTRY %main (arg: (s32[], f32[8,15])) -> (s32[], f32[8,15]) {
      %arg = (s32[], f32[8,15]) parameter(0)
      ROOT %w = (s32[], f32[8,15]) while((s32[], f32[8,15]) %arg), condition=%cond, body=%body
    }
""")


def test_collective_detail_loop_multiplied():
    r = analyse_hlo(LOOPED_ALL_REDUCE_HLO)
    detail = r["collective_detail"]
    assert set(detail) == {"all-reduce"}, detail
    payload = 8 * 15 * 4
    assert detail["all-reduce"]["count"] == 6
    assert detail["all-reduce"]["bytes"] == pytest.approx(6 * payload)
    # the aggregate fields stay consistent with the detail
    assert r["collectives"]["all-reduce"] == 6
    assert r["collective_bytes"] == pytest.approx(6 * payload)


def test_join_term_priced_from_detail():
    from repro.core import tuning

    term = tuning.join_term_from_hlo(
        LOOPED_ALL_REDUCE_HLO, device_count=8,
        profile=tuning.PROFILES["cpu"])
    assert term["count"] == 6
    assert term["bytes"] == pytest.approx(6 * 8 * 15 * 4)
    # alpha*log2(8) per psum plus payload/link_bw, all positive
    expect = (6 * tuning.PROFILES["cpu"].join_alpha * 3
              + term["bytes"] / tuning.PROFILES["cpu"].link_bw)
    assert term["seconds"] == pytest.approx(expect)
    assert term["detail"] == {"all-reduce": {"count": 6,
                                             "bytes": 6.0 * 8 * 15 * 4}}


def test_collective_detail_absent_without_collectives():
    r = analyse_hlo(textwrap.dedent("""
        HloModule plain
        ENTRY %main (x: f32[4,4]) -> f32[4,4] {
          %x = f32[4,4] parameter(0)
          ROOT %y = f32[4,4] add(f32[4,4] %x, f32[4,4] %x)
        }
    """))
    assert r["collective_detail"] == {}
    assert r["collective_bytes"] == 0.0


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import tuning
    from repro.launch.hlo_cost import analyse_hlo
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("model",))

    def f(x):
        return jax.lax.psum(jnp.sum(x, axis=-1), "model")

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(None, "model"),), out_specs=P(None),
        check_vma=False))
    hlo = fn.lower(jnp.ones((4, 64), jnp.float32)).compile().as_text()

    r = analyse_hlo(hlo)
    detail = r["collective_detail"]
    ar = {k: v for k, v in detail.items() if "all-reduce" in k}
    assert ar, (detail, r["collectives"])
    total = sum(v["count"] for v in ar.values())
    byts = sum(v["bytes"] for v in ar.values())
    assert total >= 1 and byts > 0, (total, byts)

    term = tuning.join_term_from_hlo(hlo, device_count=2)
    assert term["count"] >= 1 and term["seconds"] > 0, term
    print("OK")
""")


@pytest.mark.slow
def test_shard_map_psum_accounted():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
