"""The trace reduction: exact numbers on a hand-made trace, and sound ones on
a trace recorded on a TPU v5e by the harness (PageRank at scale 12)."""
import gzip
import shutil
from pathlib import Path

import jax
import pytest

from bench import cells, devtrace

RECORDED = Path(__file__).parent / "data" / "pr_scale12.xplane.pb.gz"


def _event(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in names.items())


def _space():
    """One superstep of 10 us: the tile step runs 1-4 us, another program
    6-7 us; the host sits in DevicePut 4-5 us and in Python otherwise."""
    device = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
        {_event(1, 1000, 3000)} {_event(2, 6000, 1000)} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
        {_event(3, 1000, 2000)} {_event(4, 3000, 1000)}
        {_event(5, 6000, 1000)} }}
      {_meta({1: "jit__jit_tile_step(1)", 2: "jit_other(2)",
              3: "%fusion.1 = f32[8] fusion()", 4: "%copy = f32[8] copy()",
              5: "%fusion.1 = f32[8] fusion()"})}
    }}"""
    host = f"""
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "main" timestamp_ns: 0
        {_event(1, 0, 10000)} {_event(2, 4000, 1000)} }}
      {_meta({1: "bench.superstep", 2: "DevicePut"})}
    }}"""
    return jax.profiler.ProfileData.from_text_proto(device + host)


def test_reduction_of_a_hand_made_trace():
    tr = devtrace.reduce_space(_space())
    assert tr["window_s"] == pytest.approx(10e-6)
    assert tr["busy_s"] == pytest.approx(4e-6)
    assert tr["supersteps"] == 1
    assert tr["kernel_s"] == pytest.approx({"jit__jit_tile_step": 3e-6,
                                            "jit_other": 1e-6})
    assert dict(tr["top_ops"]) == pytest.approx({
        "jit__jit_tile_step/%fusion.1": 2e-6,
        "jit__jit_tile_step/%copy": 1e-6, "jit_other/%fusion.1": 1e-6})
    assert dict(tr["idle_gaps"]) == pytest.approx({
        "bench.superstep/python": 4e-6, "bench.superstep/DevicePut": 2e-6})
    idle = cells.reducer("device_idle_share").reduce({"trace": tr})
    assert idle == pytest.approx(60.0)


def test_roofline_counts_real_edges_and_rows():
    gab = cells.reducer("gab_roofline")
    assert gab.least_bytes(100, 10, queries=1) == 100 * 8 + 10 * 8
    assert gab.least_bytes(100, 10, queries=2) == 100 * 12 + 10 * 16
    run = {"trace": {"kernel_s": {"jit__jit_tile_step": 2e-3,
                                  "jit_other": 5.0}},
           "traced_edges": 1000, "traced_rows": 100,
           "queries": 1, "peaks": {"hbm_bytes_per_s": 1e9}}
    # 8,800 bytes at 1 GB/s is 8.8 us of the tile step's 2 ms
    assert gab.reduce(run) == pytest.approx(100 * 8.8e-6 / 2e-3)
    assert gab.reduce(dict(run, trace={"kernel_s": {"jit_other": 1.0}})) \
        is None
    assert gab.reduce(dict(run, trace=None)) is None


def test_roofline_counts_each_weight_of_a_weighted_run():
    gab = cells.reducer("gab_roofline")
    assert gab.least_bytes(100, 10, 1, weighted=True) == 100 * 12 + 10 * 8
    run = {"trace": {"kernel_s": {"jit__jit_run_tile_stack": 2e-3}},
           "traced_edges": 1000, "traced_rows": 100, "queries": 1,
           "weighted": True, "peaks": {"hbm_bytes_per_s": 1e9}}
    # 12,800 bytes at 1 GB/s is 12.8 us of the tile step's 2 ms
    assert gab.reduce(run) == pytest.approx(100 * 12.8e-6 / 2e-3)


def test_a_trace_without_the_harness_spans_reads_nothing():
    device = """planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
      event_metadata { key: 1 value { id: 1 name: "op" } } }"""
    space = jax.profiler.ProfileData.from_text_proto(device)
    assert devtrace.reduce_space(space) is None


def test_reduction_of_the_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    tr = devtrace.reduce(str(path))
    assert tr["supersteps"] >= 2
    assert 0 < tr["busy_s"] < tr["window_s"]
    step = tr["kernel_s"]["jit__jit_tile_step"]
    assert 0 < step <= tr["busy_s"]
    assert tr["top_ops"][0][0].startswith("jit__jit_tile_step/")
    assert sum(v for _, v in tr["top_ops"]) <= tr["busy_s"] * (1 + 1e-9)
    idle = sum(v for _, v in tr["idle_gaps"])
    assert idle <= tr["window_s"] - tr["busy_s"] + 1e-9
    assert all(k.startswith("bench.") for k, _ in tr["idle_gaps"][:3])
    share = cells.reducer("device_idle_share").reduce({"trace": tr})
    assert 0 < share < 100
