"""PR 55: what the account of the start and of the compiles costs when it
is on (it is on by default), on the host it runs on.  No device is touched.

    PYTHONPATH=. python3 benchmarks/runs/pr55_cost.py            # this tree
    (cd scratch/parent && PYTHONPATH=. python3 ../../benchmarks/runs/pr55_cost.py)

Printed, each the least of 15 rounds of 20000 calls less an empty loop's (the
least: a round that shared its core reads high, never low):

* ``program_span``: ns a dispatch for ``with obs.program_span(name): pass``
  (the parent's has no thread-local: the difference is what a dispatch
  gained);
* ``span`` against ``top_span``: ns a tick or a step for the loop's top
  span (the parent has ``span`` alone);
* ``listener``: us an event of ``startup._on_duration`` for a lowering
  under a program span (counter, interval list, ring);
* ``phase``: us a phase for ``with obs.phase(name): pass``.

A tree without the account (the parent) prints the first two only.
"""
import json
import sys
import time

N, ROUNDS = 20000, 15


def per_call_ns(body):
    def once():
        t0 = time.perf_counter_ns()
        for _ in range(N):
            pass
        t1 = time.perf_counter_ns()
        for _ in range(N):
            body()
        t2 = time.perf_counter_ns()
        return ((t2 - t1) - (t1 - t0)) / N

    once()
    return min(once() for _ in range(ROUNDS))


def main():
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu import obs

    def program():
        with obs.program_span("pr55_cost"):
            pass

    def span():
        with obs.span("serve.tick", cat="serve", args=None):
            pass

    out = {"tree": mxnet_tpu.__file__,
           "program_span_ns": per_call_ns(program),
           "span_ns": per_call_ns(span)}
    if hasattr(obs, "top_span"):
        from mxnet_tpu.obs import startup

        def top():
            with obs.top_span("serve.tick", cat="serve", args=None):
                pass

        def phase():
            with obs.phase("build.pr55_cost"):
                pass

        def event():
            startup._on_duration(
                "/jax/core/compile/jaxpr_to_mlir_module_duration", 1e-6,
                fun_name="pr55_cost")

        def events():
            with obs.program_span("pr55_cost"):
                event()

        out["top_span_ns"] = per_call_ns(top)
        out["phase_us"] = per_call_ns(phase) / 1e3
        out["listener_us"] = (per_call_ns(events)
                              - out["program_span_ns"]) / 1e3
    print("pr55 cost: %s" % json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
