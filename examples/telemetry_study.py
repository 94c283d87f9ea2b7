"""Streaming telemetry and sampled tracing (DESIGN.md §9).

Two observability surfaces on the paper's SockShop deployment, all
opt-in (``telemetry="stream"``) and provably observation-only — the
golden-matrix digests are bit-identical with telemetry on or off
(tests/test_obs.py):

1. **Live metric stream** — the device seals one metric row per
   ``tel_window_ticks`` window into an on-carry ring and flushes ring
   halves through an ``io_callback`` tap *while the scan runs*; sinks
   render rows as OTel JSON or Prometheus exposition lines.  The same
   tap fires per sweep point during a batched ``run_batch`` sweep (rows
   carry a ``tag`` column), and the streamed windows reconcile exactly
   with each point's end-of-run ``QoSReport``.
2. **Sampled request tracing** — a seeded 1-in-k request sample leaves
   one span per hop in a fixed-capacity ring (exact overflow counter,
   never a silent cap).  Host-side reconstruction links the spans into
   the call tree and reproduces the engine's recorded response time
   with tolerance ZERO, two independent ways: timestamp identity and a
   float64 max-plus (tropical) closure over the span DAG — the same
   Alg 2 recurrence as ``core/critical_path.py``.

Per-phase device time comes from a profiler trace of a benchmark job
(``bench/run.py --trace 1``), not from this example.

    PYTHONPATH=src python examples/telemetry_study.py
"""
import argparse
import dataclasses

from repro.configs import sockshop
from repro.core import batch_item, summarize
from repro.obs import export, spans


TEL_KW = dict(telemetry="stream", tel_window_ticks=50, tel_windows=4,
              tel_span_k=25, tel_span_cap=2048)


def make_sim(duration_s: float, **kw):
    return sockshop.make_sim(n_clients=80, duration_s=duration_s,
                             seed=11, **TEL_KW, **kw)


def solo_stream(duration_s: float):
    print("=== 1. live metric stream (solo run, OTel JSON) ===")
    sim = make_sim(duration_s)
    with export.collecting() as col:
        sink = export.printer(export.otel_json)
        export.install(sink)
        try:
            res = sim.run()
        finally:
            export.uninstall(sink)
    export.validate_rows(col.rows)
    rep = summarize(sim, res)
    print(f"-> streamed {len(col.rows)} windows live; report agrees: "
          f"tel_windows={rep.tel_windows} tel_spans={rep.tel_spans} "
          f"tel_span_drops={rep.tel_span_drops}")
    return sim, res


def batch_stream(duration_s: float, n_points: int = 3) -> None:
    print("\n=== 2. run_batch: per-point live rows (Prometheus) ===")
    sim = make_sim(duration_s)
    rates = tuple(2.0 * 2 ** b for b in range(n_points))
    points = [dataclasses.replace(sim.params, spawn_rate=r)
              for r in rates]
    with export.collecting() as col:
        sink = export.printer(export.prometheus_line)
        export.install(sink)
        try:
            res = sim.run_batch(points)
        finally:
            export.uninstall(sink)
    export.validate_rows(col.rows)
    for b, (r, p) in enumerate(zip(rates, points)):
        mine = [row for row in col.rows if int(row["tag"]) == b]
        rep = summarize(sim, batch_item(res, b), params=p)
        streamed = int(sum(row["completed"] for row in mine))
        print(f"-> point {b} (spawn_rate={r}): {len(mine)} windows, "
              f"streamed completed {streamed} == report "
              f"{rep.completed_requests}")
        if streamed != rep.completed_requests:
            raise AssertionError(
                f"point {b}: streamed windows sum to {streamed} but the "
                f"QoS report counted {rep.completed_requests}")


def trace_study(sim, res) -> None:
    print("\n=== 3. sampled request traces vs critical path ===")
    d_max = int(sim.app.succ.shape[1])
    checks = spans.verify_traces(res.state, sim.graph, d_max)
    exact = [c for c in checks if c.exact]
    print(f"sampled completed requests reconstructed: {len(checks)} "
          f"({len(exact)} bitwise-exact, tolerance 0)")
    show = max(checks, key=lambda c: c.n_spans, default=None)
    if show is not None:
        roots = spans.trace_tree(spans.spans_of(res.state, show.req),
                                 sim.graph.n_services, d_max)
        print(f"\nrequest {show.req} (api {show.api}, "
              f"{show.n_spans} spans):")
        print(spans.format_trace(roots))
        print(f"engine response  {float(show.response):.6f} s\n"
              f"span-tree        {float(show.tree):.6f} s\n"
              f"tropical closure {float(show.tropical):.6f} s"
              + (f"\ngraph Alg 2      {float(show.graph):.6f} s"
                 if show.graph is not None else ""))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--points", type=int, default=3,
                    help="sweep points in the run_batch section")
    args = ap.parse_args()
    sim, res = solo_stream(args.duration)
    batch_stream(args.duration, args.points)
    trace_study(sim, res)


if __name__ == "__main__":
    main()
