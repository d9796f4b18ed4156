"""Reduction of a trace to busy time, kernel time and labelled idle gaps."""

import _tiny
from bench import trace as T

MS = 1_000_000


def small_trace():
    """A window of 10 ms: two decode programs, each of two ops (one of
    them the paged kernel), with the host in a pump / engine step /
    fan-out between them."""
    ops = [("fusion.1", 1 * MS, 2 * MS),
           ("flash_decode_paged custom-call.5", 2 * MS, 4 * MS),
           ("fusion.1", 6 * MS, 7 * MS),
           ("flash_decode_paged custom-call.5", 6 * MS + MS // 2, 8 * MS)]
    modules = [("jit_run_k(7)", 1 * MS, 4 * MS),
               ("jit_run_k(7)", 6 * MS, 8 * MS),
               ("jit_pre(3)", 9 * MS, 9 * MS + MS // 2)]
    host = [("bench.window", 0, 10 * MS),
            ("pump", 0, 5 * MS), ("engine_step", 0, 4 * MS),
            ("fanout", 4 * MS, 5 * MS),
            ("pump", 5 * MS, 10 * MS), ("admission", 8 * MS, 10 * MS)]
    return T.from_events(ops, modules, host)


def test_busy_is_the_union_of_ops():
    tr = small_trace()
    t0, t1 = T.window(tr)
    assert (t0, t1) == (0, 10 * MS)
    # [1,4] and [6,8] ms: the overlapping ops count once
    assert T.busy_ns(tr, t0, t1) == 5 * MS
    assert T.busy_ns(tr, 3 * MS, 7 * MS) == 2 * MS


def test_kernel_and_program_time_by_name():
    tr = small_trace()
    t0, t1 = T.window(tr)
    assert T.time_of(tr.planes[0].ops, "flash_decode_paged", t0, t1) == (
        2 * MS + MS + MS // 2, 2)
    assert T.time_of(tr.planes[0].modules, "jit_run_k", t0, t1) == (5 * MS, 2)
    assert T.time_of(tr.planes[0].modules, "jit_pre", t0, t1) == (MS // 2, 1)


def test_idle_gaps_are_labelled_by_the_host_span():
    tr = small_trace()
    t0, t1 = T.window(tr)
    assert T.gaps(tr, t0, t1) == [(0, MS), (4 * MS, 6 * MS),
                                  (8 * MS, 10 * MS)]
    b = T.breakdown(tr, t0, t1)
    assert b["idle_gaps"][0][1] == 0.002
    labels = {lab for lab, _ in b["idle_gaps"]}
    # the innermost span at each gap's midpoint: [0,1] ms in the engine
    # step, [4,6] ms between fan-out and the next pump, [8,10] ms in
    # admission
    assert labels == {"engine_step", "pump", "admission"}
    assert b["device_ops"][0] == ["flash_decode_paged", 0.0035]


def test_host_spans_of_a_recorded_trace(tmp_path):
    """The client's span names survive a real profiler trace (CPU), with
    the options the benchmark traces under (no Python tracer)."""
    import jax
    import jax.numpy as jnp
    from bench import run as R
    f = jax.jit(lambda x: jnp.sin(x).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=R.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pump"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    names = [h[0] for h in tr.host]
    assert names.count("pump") == 3 and names.count("bench.window") == 1
    t0, t1 = T.window(tr)
    assert all(t0 <= s and e <= t1 for n, s, e in tr.host if n == "pump")


def test_traced_run_exports_its_slice_beside_the_client(monkeypatch):
    """A traced run of the toy cell (CPU): the profiler's export runs on a
    thread of its own, not the client's, and the slice is read after it,
    with the client's spans in it."""
    import threading

    import jax
    from bench import run as R
    from bench import weights
    stopped_on = []
    real_stop = jax.profiler.stop_trace

    def stop_trace():
        stopped_on.append(threading.current_thread())
        real_stop()

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    cell = _tiny.cell(requests=64)     # traffic that outlasts the window
    params = weights.make_params(cell.hf, 11)
    served, run, _ = R.serve_cell(cell, params, 11, 2.0, trace=True)
    assert len(stopped_on) == 1
    assert stopped_on[0] is not threading.main_thread()
    names = [h[0] for h in run.trace.host]
    assert names.count("bench.window") == 1 and "pump" in names
    assert R.load_metric("host_pump_ms")(run) > 0
    assert served.finished
