"""The trace reduction on a synthetic trace, and on one recorded here."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import trace_reduce

US = 1_000_000  # picoseconds per microsecond


def _events(pairs, meta):
    return "".join(
        f"events {{ metadata_id: {meta[name]} offset_ps: {int(t0 * US)} "
        f"duration_ps: {int(dur * US)} }}\n" for name, t0, dur in pairs)


def synthetic():
    """Device 0: a 100 us window with ops busy 10-30 us (two overlapping
    ops) and 60-70 us; one program launch 10-30 us. Host: the window, a
    step over 0-40 us, a wait over 40-100 us."""
    meta = {"fusion.1": 1, "wnn_lookup": 2, "jit__batch_scores(1)": 3,
            "bench.window": 4, "bench.step": 5, "bench.wait": 6,
            "other": 7}
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}\n' for n, i in meta.items())
    ops = _events([("fusion.1", 10, 15), ("wnn_lookup", 20, 10),
                   ("wnn_lookup", 60, 10), ("fusion.1", 150, 5)], meta)
    mods = _events([("jit__batch_scores(1)", 10, 20),
                    ("jit__batch_scores(1)", 60, 10)], meta)
    host = _events([("bench.window", 0, 100), ("bench.step", 0, 40),
                    ("bench.wait", 40, 60), ("other", 0, 200)], meta)
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000 {mods} }}
  {md} }}
planes {{ id: 3 name: "/device:CUSTOM:Megascale Trace" }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 1000000 {host} }}
  {md} }}
"""
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


def test_busy_idle_and_window():
    r = synthetic()
    assert r.devices == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(100e-6)
    # union of [10, 25] and [20, 30] and [60, 70]; the op at 150 is outside
    assert r.busy_s == pytest.approx(30e-6)
    assert r.idle_share == pytest.approx(0.7)


def test_kernels_and_programs():
    r = synthetic()
    assert r.kernel_seconds("wnn_lookup") == pytest.approx(20e-6)
    assert r.kernel_seconds("no_such_kernel") == 0.0
    ops = dict(r.op_totals())
    assert ops["wnn_lookup"] == pytest.approx(20e-6)
    assert ops["fusion.1"] == pytest.approx(15e-6)


def test_idle_gaps_by_host_annotation():
    r = synthetic()
    gaps = sorted(r.idle_gaps())
    # 0-10 in the step, 30-60 and 70-100 in the wait
    assert [round(s * 1e6) for s, _ in gaps] == [10, 30, 30]
    labels = {round(s * 1e6): lab for s, lab in gaps}
    assert labels[10] == "bench.step"
    assert labels[30] == "bench.wait"
    b = r.breakdown()
    assert b["idle_gaps"][0][0] == "bench.wait"
    assert b["idle_gaps"][0][1] == pytest.approx(60e-6)
    assert b["device_ops"][0][0] == "wnn_lookup"


def test_recorded_trace_without_a_device(tmp_path):
    """A trace recorded on this host: the window is found, and with no
    device plane nothing counts as busy."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = trace_reduce.reduce_dir(str(tmp_path))
    assert r.window_s > 0
    assert r.devices == []
    assert r.busy_s == 0.0
    assert r.idle_gaps() == []


def test_missing_window_raises():
    text = 'planes { id: 1 name: "/host:CPU" }'
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


def test_op_label():
    hlo = ('%_local_scores.6 = s32[1024,10]{1,0:T(8,128)S(1)} custom-call('
           's32[2,12]{1,0} %copy-done.27), custom_call_target='
           '"tpu_custom_call"')
    assert trace_reduce.op_label(hlo) == "%_local_scores.6 custom-call"
    tup = ('%copy-start = (u8[128,5488]{0,1:T(8,128)(4,1)S(1)}, u32[]{:S(2)})'
           ' copy-start(u8[128,5488]{0,1:T(8,128)(4,1)} %bits.1)')
    assert trace_reduce.op_label(tup) == "%copy-start copy-start"
    assert trace_reduce.op_label("fusion.1") == "fusion.1"
