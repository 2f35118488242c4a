"""Trace reduction: busy union and idle share, module time, collective
time and its exposed part, and the breakdown, on hand-made traces and on
small traces recorded on a v5e."""
from __future__ import annotations

import pytest

from bench import trace
from perfbench_roots import DATA

MS = 1_000_000          # ns


def hand_trace():
    # window 0..100 ms; device 0: compute 0-30, all-to-all 20-50 (10 ms
    # exposed: 30-40 is not covered... see below), compute 40-60, idle
    # 60-90, compute 90-100.  Device 1: compute 0-50, idle 50-100.
    d0 = [("fusion.1", 0, 30 * MS), ("all-to-all.3", 20 * MS, 30 * MS),
          ("fusion.2", 40 * MS, 20 * MS), ("fusion.1", 90 * MS, 10 * MS)]
    d1 = [("fusion.1", 0, 50 * MS)]
    mods = [("jit_step(1)", 0, 60 * MS), ("jit_step(1)", 90 * MS, 10 * MS),
            ("jit_other(2)", 95 * MS, 1 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": d0, "modules": mods},
                        "/device:TPU:1": {"ops": d1, "modules": mods[:1]}},
            "spans": [("window", 0, 100 * MS), ("step", 0, 60 * MS),
                      ("idle", 60 * MS, 30 * MS), ("step", 90 * MS, 10 * MS)]}


def test_busy_union_and_idle_share():
    s = trace.TraceSummary(hand_trace())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s("/device:TPU:0") == pytest.approx(0.07)
    assert s.busy_s("/device:TPU:1") == pytest.approx(0.05)
    assert s.idle_share() == pytest.approx(1 - 0.06 / 0.1)


def test_collective_time_and_exposed_part():
    s = trace.TraceSummary(hand_trace())
    total, exposed = s.collective_s()
    # device 0: 30 ms of all-to-all, of which 30-40 ms ran alone; device 1
    # none: averaged over the two devices
    assert total == pytest.approx(0.015)
    assert exposed == pytest.approx(0.005)


def test_module_time():
    s = trace.TraceSummary(hand_trace())
    secs, count = s.module_time("jit_step")
    assert count == 2            # 2 on one device, 1 on the other
    assert secs == pytest.approx((0.07 + 0.06) / 2)
    assert s.module_time("jit_other") == (pytest.approx(0.001), 1)
    assert s.module_time("absent") == (0.0, 0)


def test_module_compute_time():
    s = trace.TraceSummary(hand_trace())
    # device 0: jit_step 0-60 holds compute 0-30 and 40-60 (the all-to-all
    # alone 30-40 is left out), and 90-100; device 1: compute 0-50 of 0-60
    secs, count = s.module_compute_s("jit_step")
    assert count == 2
    assert secs == pytest.approx((0.06 + 0.05) / 2)
    # jit_other 95-96 lies inside compute 90-100 on device 0
    assert s.module_compute_s("jit_other") == (pytest.approx(0.001), 1)
    assert s.module_compute_s("absent") == (0.0, 0)


def test_breakdown_labels_gaps_by_host_span():
    b = trace.TraceSummary(hand_trace()).breakdown()
    assert b["idle_gaps"][0] == ["idle", pytest.approx(0.03)]
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "fusion.1" and "all-to-all.3" in names
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_clips_events():
    data = hand_trace()
    data["devices"]["/device:TPU:0"]["ops"].append(("fusion.9", 95 * MS,
                                                    50 * MS))
    s = trace.TraceSummary(data)
    assert s.busy_s("/device:TPU:0") == pytest.approx(0.07)


def test_trace_without_window_span_is_refused():
    data = hand_trace()
    data["spans"] = [s for s in data["spans"] if s[0] != "window"]
    with pytest.raises(ValueError):
        trace.TraceSummary(data)


def test_collective_names():
    assert trace.is_collective("all-to-all.12")
    assert trace.is_collective("all-reduce-start.1")
    assert not trace.is_collective("fusion.3")


@pytest.mark.parametrize("text,collective", [
    ("%all_to_all.7 = f32[4,1,807803]{2,1,0:T(1,128)S(1)} all-to-all("
     "f32[4,1,807803]{2,1,0:T(1,128)S(1)} %all_to_all.6), channel_id=1",
     True),
    ("%all_to_all.6 = f32[4,1,807803]{2,1,0:T(1,128)S(1)} reshape("
     "f32[3231212]{0:T(1024)S(1)} %fusion.1)", False),
    ("%reduce.1 = f32[4,807803]{1,0:T(4,128)S(1)} reduce(f32[4,1,807803]"
     "{2,1,0:T(1,128)S(1)} %all_to_all.7, f32[]{:T(128)} %constant.38)",
     False),
    ("%all-reduce = f32[]{:T(128)} all-reduce(f32[]{:T(128)} "
     "%abs_reduce_fusion), channel_id=1", True),
    ("%while.9 = (u32[]{:T(128)}, f32[67108864]{0:T(1024)}) while((u32[]"
     "{:T(128)}, f32[67108864]{0:T(1024)}) %tuple)", False),
])
def test_collective_by_hlo_opcode(text, collective):
    """TPU op events carry the instruction's HLO text: the opcode decides,
    not a name that mentions a collective."""
    assert trace.is_collective(text) is collective


def test_short_names_drop_layouts():
    short, opcode = trace.op_info(
        "%fusion.2 = f32[62914560]{0:T(1024)} fusion(f32[3231212]{0:T(1024)"
        "S(1)} %reshape.18), kind=kCustom")
    assert short == ("%fusion.2 = f32[62914560] fusion(f32[3231212] "
                     "%reshape.18), kind=kCustom")
    assert opcode == "fusion"


def test_spmv_chip_trace():
    """A 2-step window of spmv_mesh.4chip recorded on a v5e 2x2: four
    devices, one step program per step, the all-to-all of the exchange and
    the all-reduce of the rescale as the only collectives."""
    s = trace.TraceSummary(trace.load(DATA / "spmv_trace.json.gz"))
    assert s.devices == [f"/device:TPU:{i}" for i in range(4)]
    steps = sum(1 for n, *_ in s.spans if n == "step")
    assert steps == 2
    assert 0 < s.mean_busy_s() <= s.window_s
    assert 0 <= s.idle_share() < 0.01
    secs, count = s.module_time("jit_step_local")
    assert count == steps and 0 < secs < s.window_s
    compute, count = s.module_compute_s("jit_step_local")
    assert count == steps and 0.99 * secs < compute < secs
    total, exposed = s.collective_s()
    assert 0 < exposed <= total < 1e-3 * steps
    colls = {trace.op_info(n)[1] for n, *_ in
             s.data["devices"]["/device:TPU:0"]["ops"] if trace.is_collective(n)}
    assert colls == {"all-to-all", "all-reduce"}
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert all(label == "step" for label, _ in b["idle_gaps"])


def test_serve_chip_trace():
    """1.5 s of the Mixtral serving loop on one v5e: the decode program per
    engine step, prefill chunks, the insert of admitted requests, idle
    gaps labelled by the engine step the host was in, no collectives."""
    s = trace.TraceSummary(trace.load(DATA / "serve_trace.json.gz"))
    assert s.devices == ["/device:TPU:0"]
    steps = sum(1 for n, *_ in s.spans if n == "engine.step")
    dsecs, decodes = s.module_time("jit_decode_step")
    psecs, chunks = s.module_time("jit_prefill")
    assert decodes == steps == 44
    assert 0.015 < dsecs / decodes < 0.025          # ~19 ms a decode
    assert chunks > 0 and 0.010 < psecs / chunks < 0.025
    assert s.module_time("jit__insert")[1] > 0
    assert 0 < s.idle_share() < 0.2
    assert s.collective_s() == (0.0, 0.0)
    gaps = s.breakdown()["idle_gaps"]
    assert gaps and all(label == "engine.step" for label, _ in gaps)


def test_spmv_step_roofline_reads_device_time():
    """On the recorded window the roofline share divides the least time of
    a product (587,202,560 bytes a chip at 819 GB/s) by the device time of
    the product program's compute, about 1,720 ms, not by the host's step
    time."""
    import importlib.util
    from types import SimpleNamespace

    from bench.peaks import peaks_for
    from perfbench_roots import REPO

    path = REPO / "bench/metrics/spmv.step_roofline.py"
    spec = importlib.util.spec_from_file_location("step_roofline", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    s = trace.TraceSummary(trace.load(DATA / "spmv_trace.json.gz"))
    counters = {"steps": 2, "n": 2**24, "p": 4, "r_nz": 16}
    ctx = SimpleNamespace(
        trace=s, peaks=peaks_for("TPU v5 lite"),
        measured=SimpleNamespace(counters=counters,
                                 end_to_end={"step_ms": 1e9}))
    compute, count = s.module_compute_s("step_local")
    value = reader.read(ctx)
    assert value == pytest.approx(100 * 587202560 / 819e9 * count / compute)
    assert 0.040 < value < 0.043
    ctx.peaks = None
    assert reader.read(ctx) is None
