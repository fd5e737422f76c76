"""PR 57, the refusal round: is a stop of the serving loop the machine's or
the process's?  One run of a cell exactly as ``python3 -m chipbench.run``
makes it, its long ticks kept with their times on the machine's monotonic
clock, beside three sentinel processes that do nothing but read that clock:
``small`` sleeps 2 ms a wake, ``big`` the same after touching ``BIG_GB`` of
memory, ``spin`` never sleeps.  A gap of a sentinel that falls inside a long
tick of the loop means the whole machine stood still; a long tick that no
sentinel saw is the process's own.

    python3 benchmarks/runs/pr57_stops.py --workload olmoh_serve_rollouts \
        --seed 5700000601 --seconds 51 --trace 0
    python3 benchmarks/runs/pr57_stops.py --idle 170    # the sentinels alone
    python3 benchmarks/runs/pr57_stops.py --phases 80   # what brings a stop

``--phases S`` runs one small program back to back on the chip for S seconds
a phase, the sentinels beside it: ``compute`` waits for each result and moves
nothing, ``readback`` also reads 96 numbers back a step, ``both`` also sends
a table of 96 x 256 numbers in first, ``idle`` leaves the chip alone.  The
stops of each phase are counted.

Nothing of the benchmark is edited: the stamps are taken where
``chipbench.timing.segment_rates`` is handed them after the window.  Written
to ``chiprun_out/pr57_stops/<cell>-<seed>.json``.
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.getcwd())

BIG_GB = 6
LONG_MS = 60.0      # a tick or a sentinel's gap this long is kept

SENTINEL = r"""
import sys, time, json
kind, gb, out = sys.argv[1], float(sys.argv[2]), sys.argv[3]
if gb:
    import numpy as np
    held = np.ones(int(gb * 2 ** 30 // 8))
gaps, last = [], time.perf_counter()
end = last + float(sys.argv[4])
while last < end:
    if kind != "spin":
        time.sleep(0.002)
    now = time.perf_counter()
    if now - last > %f:
        gaps.append((last, now))
    last = now
json.dump(gaps, open(out, "w"))
""" % (LONG_MS / 1e3)


def phases(seconds, out, kinds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    step = jax.jit(lambda x, t: (jnp.tanh(x @ x) + t.sum() * 1e-9, x[0, :96]))
    n = int(os.environ.get("PR57_N", "8192"))   # 5.6 ms a step on a v5e
    x = jnp.ones((n, n), jnp.bfloat16) * 1e-3
    table = np.zeros((96, 256), np.int32)
    zero = jnp.zeros((96, 256), jnp.int32)
    jax.block_until_ready(step(x, zero))
    order = ("compute", "readback", "both", "idle", "both", "readback",
             "compute")
    procs = [subprocess.Popen(
        [sys.executable, "-c", SENTINEL, kind, str(gb),
         os.path.join(out, "phases-%s.json" % kind),
         str(seconds * len(order) + 5)]) for kind, gb in kinds]
    marks = []
    for phase in order:
        began, n = time.perf_counter(), 0
        while time.perf_counter() - began < seconds:
            if phase == "idle":
                time.sleep(0.01)
                continue
            t = jax.device_put(table) if phase == "both" else zero
            x, row = step(x, t)
            if phase == "compute":
                jax.block_until_ready(x)
            else:
                np.asarray(row)
            n += 1
        marks.append((phase, began, time.perf_counter(), n))
    for p in procs:
        p.wait()
    for kind, _ in kinds:
        gaps = json.load(open(os.path.join(out, "phases-%s.json" % kind)))
        for phase, a, b, n in marks:
            print("%s, %s (%d steps, %.1f s): %s" % (
                kind, phase, n, b - a, json.dumps(
                    [(round(x0 - a, 2), round(1e3 * (y0 - x0), 1))
                     for x0, y0 in gaps if a <= x0 < b])), flush=True)
    return 0


def main():
    argv = sys.argv[1:]
    value = lambda flag: argv[argv.index(flag) + 1]
    out = "chiprun_out/pr57_stops"
    os.makedirs(out, exist_ok=True)
    if "--phases" in argv:
        return phases(float(value("--phases")), out, (("small", 0),))
    idle = "--idle" in argv
    name = "idle" if idle \
        else "%s-%s" % (value("--workload"), value("--seed"))
    life = float(value("--idle")) if idle \
        else float(os.environ.get("PR57_SENTINEL_S", "170"))
    kinds = (("small", 0), ("big", BIG_GB), ("spin", 0))
    procs = [subprocess.Popen(
        [sys.executable, "-c", SENTINEL, kind, str(gb),
         os.path.join(out, "%s-%s.json" % (name, kind)), str(life)])
        for kind, gb in kinds]
    if idle:    # no process touches the chip: are there stops all the same?
        began = time.perf_counter()
        for p in procs:
            p.wait()
        for kind, _ in kinds:
            gaps = json.load(open(os.path.join(
                out, "%s-%s.json" % (name, kind))))
            print("idle %s: %s" % (kind, json.dumps(
                [(round(x - began, 3), round(1e3 * (y - x), 1))
                 for x, y in gaps])), flush=True)
        return 0
    from chipbench import run, timing

    kept = {}
    plain = timing.segment_rates

    def keep(stamps, t0, units, *a, **kw):
        kept.update(t0=t0, stamps=list(stamps))
        return plain(stamps, t0, units, *a, **kw)

    timing.segment_rates = keep
    rc = run.main(argv)
    for p in procs:
        p.wait()
    if rc or not kept:
        return rc
    t0, stamps = kept["t0"], kept["stamps"]
    edges = [t0] + stamps
    long = [(i, a, b) for i, (a, b) in enumerate(zip(edges, edges[1:]))
            if 1e3 * (b - a) > LONG_MS]
    seen = {kind: json.load(open(os.path.join(
        out, "%s-%s.json" % (name, kind)))) for kind, _ in kinds}
    rows = []
    for i, a, b in long:
        row = {"tick": i, "at_s": round(a - t0, 3),
               "ms": round(1e3 * (b - a), 1)}
        for kind, gaps in seen.items():
            inside = [round(1e3 * (y - x), 1) for x, y in gaps
                      if x < b and y > a]
            row[kind] = inside
        rows.append(row)
    window = {kind: [(round(x - t0, 3), round(1e3 * (y - x), 1))
                     for x, y in gaps if t0 <= x <= stamps[-1]]
              for kind, gaps in seen.items()}
    whole = {kind: [(round(x - t0, 3), round(1e3 * (y - x), 1))
                    for x, y in gaps] for kind, gaps in seen.items()}
    result = {"ticks": len(stamps), "window_s": stamps[-1] - t0,
              "long_ticks": rows, "sentinel_gaps_in_window": window,
              "sentinel_gaps_all": whole}
    with open(os.path.join(out, name + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print("stops: %s" % json.dumps({k: result[k] for k in (
        "ticks", "long_ticks", "sentinel_gaps_in_window")}), flush=True)
    print("sentinels, whole life: %s" % json.dumps(whole), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
