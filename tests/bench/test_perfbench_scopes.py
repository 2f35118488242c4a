"""Scope reduction (``bench.scopes``): the device time split by the
program's named scopes, idle gaps labelled by its spans, the op_name join
from HLO text; the readers of the program's telemetry; the split tool on
four CPU devices; and the recorded chip traces."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import harness, scopes, trace
from perfbench_roots import DATA, REPO, make_root

MS = 1_000_000          # ns
STEP = "jit_step_local(1)"


def scoped_trace():
    # window 0..100 ms, one step module 0..60 ms on both devices.  Device 0:
    # pack 0-10, all-to-all 10-20 (collective: left out), a while loop
    # 20-50 under comm.unpack holding a dynamic-update-slice 25-35 under
    # spmv.local/own (the innermost op takes its instants), an unscoped
    # copy 50-55, then a rescale op outside the module 70-80.  Device 1:
    # spmv.local 0-40.
    ops0 = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 10 * MS),
            ("%all-to-all.3 = f32[8] all-to-all(f32[8] %fusion.1)",
             10 * MS, 10 * MS),
            ("%while.2 = (f32[8]) while((f32[8]) %t)", 20 * MS, 30 * MS),
            ("%dynamic-update-slice.4 = f32[8] dynamic-update-slice()",
             25 * MS, 10 * MS),
            ("%copy.5 = f32[8] copy(f32[8] %a)", 50 * MS, 5 * MS),
            ("%divide.1 = f32[8] divide(f32[8] %a)", 70 * MS, 10 * MS)]
    names0 = ["jit(step_local)/shard_map/comm.pack/gather",
              "jit(step_local)/shard_map/comm.exchange/all_to_all",
              "jit(step_local)/shard_map/comm.unpack/while",
              "jit(step_local)/shard_map/spmv.local/own/gather",
              "", "jit(<lambda>)/div"]
    ops1 = [("%fusion.9 = f32[8] fusion()", 0, 40 * MS)]
    mods = [(STEP, 0, 60 * MS), ("jit__lambda(2)", 70 * MS, 10 * MS)]
    return {"devices": {
        "/device:TPU:0": {"ops": ops0, "modules": mods, "op_names": names0},
        "/device:TPU:1": {"ops": ops1, "modules": mods[:1],
                          "op_names": ["jit(step_local)/spmv.local/mul"]}},
        "spans": [("window", 0, 100 * MS), ("step", 0, 60 * MS),
                  ("step", 60 * MS, 40 * MS)],
        "program_spans": [("spmv.call", 0, 2 * MS),
                          ("plan.load", 54 * MS, 20 * MS)]}


def test_scope_of_takes_the_innermost_name():
    assert scopes.scope_of("jit(f)/comm.unpack/gather") == "comm.unpack"
    assert scopes.scope_of("jit(f)/comm.pack/x/comm.exchange/all_to_all") \
        == "comm.exchange"
    assert scopes.scope_of("jit(f)/spmv.local/own/gather") == \
        "spmv.local/own"
    assert scopes.scope_of("jit(f)/spmv.local/mul") == "spmv.local"
    assert scopes.scope_of("jit(f)/mul") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_scope_split_partitions_module_compute_time():
    s = scopes.ScopedSummary(scoped_trace())
    split = s.scope_split("step_local")
    # device 0: pack 10, unpack 30 - 10 nested, own 10, unscoped 5 ms;
    # device 1: spmv.local 40 ms; averaged over the two devices
    assert split == {"comm.pack": pytest.approx(0.005),
                     "comm.unpack": pytest.approx(0.010),
                     "spmv.local": pytest.approx(0.020),
                     "spmv.local/own": pytest.approx(0.005),
                     "unscoped": pytest.approx(0.0025)}
    compute, count = s.module_compute_s("step_local")
    assert count == 1
    assert sum(split.values()) == pytest.approx(compute)


def test_scope_s_counts_parts_and_executions():
    s = scopes.ScopedSummary(scoped_trace())
    assert s.scope_s("spmv.local", "step_local") == (
        pytest.approx((0.010 + 0.040) / 2), 1)
    assert s.scope_s("comm.unpack", "step_local")[0] == pytest.approx(0.010)
    assert s.scope_s("unscoped", "step_local")[0] == pytest.approx(0.0025)
    assert s.scope_s("comm.pack", "absent") == (0.0, 0)


def test_idle_gaps_get_program_labels_and_breakdown_only_grows():
    data = scoped_trace()
    s, plain = scopes.ScopedSummary(data), trace.TraceSummary(data)
    gaps = s.idle_gaps_program()
    assert [g[0] for g in gaps] == ["plan.load", "none"]
    assert [g[1] for g in gaps] == [pytest.approx(0.015),
                                    pytest.approx(0.02)]
    b, old = s.breakdown(), plain.breakdown()
    assert {k: b[k] for k in old} == old
    assert set(b) - set(old) == {"device_scopes", "idle_gaps_program"}
    assert b["idle_gaps_program"][0] == ["none", pytest.approx(0.02)]
    assert dict(b["device_scopes"])["spmv.local"] == pytest.approx(0.02)
    assert dict(b["device_scopes"])["unscoped"] == pytest.approx(0.0075)


def test_join_hlo_names_ops_inside_the_module():
    data = scoped_trace()
    dev = data["devices"]["/device:TPU:0"]
    dev["op_names"] = [""] * len(dev["ops"])
    hlo = "\n".join([
        'ENTRY %main {',
        '  %fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop, '
        'metadata={op_name="jit(step_local)/comm.pack/gather" '
        'source_file="x.py" source_line=3}',
        '  ROOT %divide.1 = f32[8] divide(f32[8] %a), '
        'metadata={op_name="jit(step_local)/spmv.local/div"}',
        '}'])
    assert scopes.hlo_op_names(hlo) == {
        "fusion.1": "jit(step_local)/comm.pack/gather",
        "divide.1": "jit(step_local)/spmv.local/div"}
    # the rescale module's divide.1 runs outside step_local: left unnamed
    assert scopes.join_hlo(data, hlo, "step_local") == 1
    assert dev["op_names"][0].endswith("comm.pack/gather")
    assert dev["op_names"][5] == ""


def test_recorded_trace_without_scopes_keeps_its_numbers():
    data = trace.load(DATA / "spmv_trace.json.gz")
    s, plain = scopes.ScopedSummary(data), trace.TraceSummary(data)
    assert not s.has_scopes()
    assert s.idle_share() == plain.idle_share()
    assert s.collective_s() == plain.collective_s()
    assert s.module_compute_s("step_local") == \
        plain.module_compute_s("step_local")
    split = s.scope_split("step_local")
    assert set(split) == {scopes.UNSCOPED}
    compute, count = plain.module_compute_s("step_local")
    assert split[scopes.UNSCOPED] == pytest.approx(compute / count)


def _reader(name):
    return harness.load_module(
        harness.find_file(REPO, "metrics", name, ".py"), "bench_metric")


@pytest.mark.parametrize("name,key", [("spmv.plan_load_s", "plan.load"),
                                      ("spmv.compile_s", None)])
def test_program_readers(name, key, monkeypatch):
    from repro.comm import telemetry

    reader = _reader(name)
    with telemetry.isolated() as tel:
        if key:
            assert reader.read(None) == 0.0       # every plan was built
            with telemetry.span(key) as sp:
                pass
            assert reader.read(None) == sp.seconds
        else:
            tel.add_compile(0.25)
            tel.add_compile(0.5)
            assert reader.read(None) == 0.75
        # a program whose telemetry has no spans or compile counters (the
        # parent's) gives nothing, and does not raise
        old = {"sources": {}, "build_seconds": {}, "ticks": {}, "total": 0}
        monkeypatch.setattr(tel, "snapshot", lambda: dict(old))
        assert reader.read(None) is None


def test_split_tool_on_four_cpu_devices(tmp_path):
    root = make_root(tmp_path / "root")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "split.py"), "--root",
         str(root), "--workload", "spmv_tiny.4chip", "--seed", "3000000001",
         "--seconds", "0.5", "--allow-cpu"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["compiles_in_window"] == 0
    assert out["setup_compiles"] > 0 and out["setup_compile_s"] > 0
    # the spans tile the engine's construction (the harness's plan span)
    assert 0.9 * out["plan_s"] <= out["setup_spans_sum"] <= out["plan_s"]
    assert {"plan.key", "plan.destination", "spmv.place"} <= set(
        out["setup_spans"])
    assert "spmv.call" not in out["setup_spans"]
    assert "the trace holds no device ops" in proc.stderr


def test_recorded_scoped_chip_trace():
    """Two steps of spmv_mesh.4chip on a v5e 2x2, recorded by
    ``bench/split.py --trace-out`` (op_names joined from the compiled
    module's HLO text): the scopes cover the product program."""
    s = scopes.ScopedSummary(trace.load(DATA / "spmv_scoped_trace.json.gz"))
    assert s.has_scopes() and len(s.devices) == 4
    split = s.scope_split("step_local")
    compute, count = s.module_compute_s("step_local")
    assert count == 2
    assert sum(split.values()) == pytest.approx(compute / count)
    main = sum(v for k, v in split.items()
               if k.split("/")[0] in ("comm.pack", "comm.unpack",
                                      "spmv.local"))
    assert main >= 0.98 * compute / count
    # the overlap rung: unpack (the Destination gathers) is most of the
    # step, the own partial most of the local product
    assert split["comm.unpack"] > split["spmv.local/own"] > \
        split["comm.pack"] > split["spmv.local/foreign"]
    secs, runs = s.scope_s("spmv.local", "step_local")
    assert runs == 2 and secs / runs == pytest.approx(
        split["spmv.local"] + split["spmv.local/own"]
        + split["spmv.local/foreign"])
    assert s.collective_s()[0] == pytest.approx(2 * 0.000147, rel=0.05)
    b = s.breakdown()
    assert b["device_scopes"][0][0] == "comm.unpack"
    assert len(b["idle_gaps_program"]) == len(b["idle_gaps"])
