"""PR 55: a compile forced inside a traced serving window, and where the
account puts it.

Serves ``opt_serve_backlog``'s configuration as ``chipbench/drivers/
serve_ticks.py`` does (its builder, its weights, its backlog, its fill),
opens a traced window of 64 ticks marked as the harness marks them, and
after the 16th tick halves the server's chunk width: the next chunk is a
dispatch at a new shape, so its program is traced, lowered and compiled
inside the ``serve.tick`` that needs it.  Printed:

* the ``compile.*`` spans of the window with their program's name, and the
  ``serve.tick`` (its ``tick`` argument) and the program span that hold
  each;
* ``mx_compiles_total`` before and after the window;
* ``chipbench.spans.idle_by_span`` of the traced window, ms: the device's
  idle time under ``compile.*`` beside the other spans', with no edit to
  the harness.

    chiprun -- python3 benchmarks/runs/pr55_inwindow.py [seed]
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())


def inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def main():
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 5500000301
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    import jax

    import mxnet_tpu as mx
    from chipbench import harness, manifest, spans
    from chipbench import traffic as traffic_mod
    from chipbench import weights
    from chipbench.drivers import serve_ticks
    from mxnet_tpu import obs

    dev = jax.devices()[0]
    print("device: %s %s" % (dev.platform, dev.device_kind), flush=True)
    if dev.platform != "tpu":
        return 1
    loaded = manifest.load_cell("opt_serve_backlog")
    cfg, traffic = loaded["config"], loaded["traffic"]
    ctx = mx.tpu(0)
    sym = harness.build_symbol(cfg)
    params = weights.make_params(serve_ticks.weight_shapes(sym, cfg), cfg,
                                 seed, cfg["serve_dtype"])
    pred, server = serve_ticks.build_server(
        sym, traffic, {n: mx.nd.NDArray(v, ctx) for n, v in params.items()},
        ctx)
    for p, o in traffic_mod.backlog(traffic, cfg["vocab_size"], seed):
        server.submit(p, max_new_tokens=o)
    ps = server.serve_open()
    while len(ps["active"]) < int(traffic["slots"]):
        server.serve_tick()
    for _ in range(8):
        server.serve_tick()
    jax.block_until_ready(ps["state"])

    def total():
        fam = obs.registry.snapshot()["mx_compiles_total"]["series"]
        return {"%s/%s" % (r["labels"]["program"], r["labels"]["cache"]):
                r["value"] for r in fam}

    before = total()
    tracer = harness.Tracer(True, "pr55_inwindow")
    tracer.start()
    for i in range(64):
        if i == 16:
            server._chunk_w //= 2       # the new shape
        with tracer.span("serve_tick"):
            server.serve_tick()
    jax.block_until_ready(ps["state"])
    tracer.stop(harness.Phases())
    after = total()
    print("mx_compiles_total, risen in the window: %s" % json.dumps(
        {k: v - before.get(k, 0) for k, v in after.items()
         if v != before.get(k, 0)}), flush=True)

    ev = obs.timeline.events()
    ticks = [e for e in ev if e["name"] == "serve.tick"][-64:]
    programs = [e for e in ev if e["cat"] == "program"]
    for e in ev:
        if e["cat"] != "compile" or e["ts"] < ticks[0]["ts"]:
            continue
        tick = [t["args"]["tick"] for t in ticks if inside(e, t)]
        held = [p["name"] for p in programs if inside(e, p)]
        print("%-18s %8.1f ms program=%s fun=%s inside serve.tick %s, "
              "program span %s" % (e["name"], e["dur"] / 1e3,
                                   e["args"]["program"], e["args"]["fun"],
                                   tick, held), flush=True)
    facts = {"trace": tracer.parsed, "cell": {"name": "pr55_inwindow"}}
    idle = spans.idle_by_span(facts, "serve")
    print("idle_by_span (ms): %s" % json.dumps(
        {str(k): round(v / 1e6, 3) for k, v in sorted(
            (idle or {}).items(), key=lambda kv: -kv[1])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
