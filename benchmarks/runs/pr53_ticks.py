"""PR 53, the review round: one run of a serving cell exactly as ``python3 -m
chipbench.run`` makes it (the same arguments, the same process), with every
tick's stamp kept and the machine watched from a second thread, so that a
stalled tick can be laid beside what the process and the host did meanwhile.

    python3 benchmarks/runs/pr53_ticks.py --workload solar2_serve_agent \
        --seed 5300000701 --seconds 51 --trace 0

The watcher also notes where the loop's thread stands at each wake (its
three innermost frames and those of this repository), so that a long tick
that is the loop's own work shows what the work is (``stacks`` of each
``longest``).  With ``PR53_LOG_COMPILES=1`` jax also logs what it traces and
compiles (its ``jax_explain_cache_misses`` is left off: under it
``decode._when``'s ``eval_shape`` raises in jax 0.9.0); the records that fall
inside the window are printed with their tick.  Nothing of the benchmark is
edited: the stamps are taken where ``chipbench.timing.segment_rates`` is
handed them after the window, and the watcher wakes 20 times a second to read
the clock, the process's CPU time and the first line of ``/proc/stat`` (some
40 us a wake; the thread's ``schedstat`` reads nothing on the chip's machine).
Written to ``$PR53_TICKS_DIR`` (default ``chiprun_out/pr53_ticks``) as
``<cell>-<seed>.json``: every tick's length in ms, and for each tick that is
neither a decode tick nor a chunk tick of a usual length (``odd``) its
index, its length, the longest silence of the watcher inside it (a watcher
that went silent too means the whole process stood still: descheduled or
frozen, not waiting in a call), the process's CPU seconds inside it, and the
host's stolen and idle jiffies inside it; the ten longest ticks of any kind
likewise (``longest``: a cell whose chunk ticks are short has its stalls
taken for chunks).  The last lines printed are the summary the scripts quote,
the longest ticks as ``[index, ms, watcher's silence]`` and where the loop
stood in the four longest.
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.getcwd())

WAKE_S = 0.05
USUAL = 1.25    # a tick up to this many times its kind's median is usual


def kinds(ms):
    """``(decode, chunk, kind a tick)``: the two usual lengths (the median of
    all ticks, and the median of those over three times it) and each tick as
    ``decode`` (up to USUAL times that length), ``chunk`` (from 0.8 to USUAL
    times that length) or odd: ``decode+`` and ``chunk+``, a tick longer
    than the kind it is taken for by what it lost."""
    import statistics

    decode = statistics.median(ms)
    long = [m for m in ms if m > 3 * decode]
    chunk = statistics.median(long) if long else 6 * decode

    def kind(m):
        if m <= USUAL * decode:
            return "decode"
        if m < 0.8 * chunk:
            return "decode+"
        return "chunk" if m <= USUAL * chunk else "chunk+"

    return decode, chunk, [kind(m) for m in ms]


def main():
    from chipbench import run, timing

    kept, samples = {}, []
    plain = timing.segment_rates

    def keep(stamps, t0, units, *a, **kw):
        kept.update(t0=t0, stamps=list(stamps), units=list(units))
        return plain(stamps, t0, units, *a, **kw)

    timing.segment_rates = keep
    stop = threading.Event()

    main = threading.main_thread().ident

    def watch():
        while not stop.wait(WAKE_S):
            with open("/proc/stat") as f:
                cpu = [int(x) for x in f.readline().split()[1:9]]
            frame, where, depth = sys._current_frames().get(main), [], 0
            while frame is not None and len(where) < 12:
                name = frame.f_code.co_filename
                if depth < 3 or "mxnet_tpu/" in name or "chipbench/" in name:
                    where.append("%s:%d:%s" % (
                        "/".join(name.split("/")[-2:]), frame.f_lineno,
                        frame.f_code.co_name))
                frame, depth = frame.f_back, depth + 1
            samples.append((time.perf_counter(), time.process_time(), cpu,
                            " < ".join(where)))

    threading.Thread(target=watch, daemon=True).start()
    logged = []
    if os.environ.get("PR53_LOG_COMPILES"):
        # what jax traced or compiled, and why its cache missed, stamped:
        # afterwards, the records that fell inside the window, by tick
        import logging

        import jax

        class Keep(logging.Handler):
            def emit(self, record):
                logged.append((time.perf_counter(), record.name,
                               record.getMessage()[:1500]))

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(Keep())
        logging.getLogger("jax").propagate = False
    argv = sys.argv[1:]
    rc = run.main(argv)
    stop.set()
    if rc or not kept:
        return rc
    t0, stamps = kept["t0"], kept["stamps"]
    import bisect
    for t, name, msg in logged:
        if t0 <= t <= stamps[-1]:
            print("in window, tick %d (%.3f s in): %s: %s"
                  % (bisect.bisect_left(stamps, t), t - t0, name, msg),
                  flush=True)
    print("logged: %d records, %d before the window"
          % (len(logged), sum(t < t0 for t, _, _ in logged)), flush=True)
    ms = [1e3 * (b - a) for a, b in zip([t0] + stamps[:-1], stamps)]
    decode, chunk, kind = kinds(ms)
    usual = {"decode+": decode, "chunk+": chunk}
    def described(i, m, k, stacks=False):
        lo, hi = (t0 if i == 0 else stamps[i - 1]), stamps[i]
        inside = [s for s in samples if lo - WAKE_S <= s[0] <= hi + WAKE_S]
        silence = max((b[0] - a[0] for a, b in zip(inside, inside[1:])),
                      default=None)
        entry = {"tick": i, "ms": round(m, 2), "kind": k,
                 "watcher_longest_silence_ms":
                     None if silence is None else round(1e3 * silence, 1)}
        if len(inside) > 1:
            first, last = inside[0], inside[-1]
            entry["process_cpu_s"] = round(last[1] - first[1], 3)
            entry["host_jiffies"] = {
                n: last[2][j] - first[2][j]
                for n, j in (("user", 0), ("system", 2), ("idle", 3),
                             ("iowait", 4), ("steal", 7))}
            entry["watched_s"] = round(last[0] - first[0], 3)
        if stacks:  # where the loop stood at the wakes strictly inside
            entry["stacks"] = [s[3] for s in samples if lo <= s[0] <= hi]
        return entry

    odd = [described(i, m, k) for i, (m, k) in enumerate(zip(ms, kind))
           if k in usual]
    lost = sum(e["ms"] - usual[e["kind"]] for e in odd)
    # whatever kind they were taken for (a cell whose chunk ticks are short
    # has its stalls taken for chunks): the ten longest ticks, described
    longest = [described(i, ms[i], kind[i], stacks=True) for i in
               sorted(range(len(ms)), key=lambda i: -ms[i])[:10]]
    value = lambda flag: argv[argv.index(flag) + 1]
    out = os.environ.get("PR53_TICKS_DIR", "chiprun_out/pr53_ticks")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, "%s-%s.json" % (value("--workload"),
                                             value("--seed")))
    window = [s for s in samples if t0 <= s[0] <= stamps[-1]]
    summary = {
        "ticks": len(ms), "decode_ms": round(decode, 3),
        "chunk_ms": round(chunk, 3), "chunk_ticks": kind.count("chunk"),
        "odd_ticks": len(odd), "lost_ms": round(lost, 1),
        "longest_ms": round(max(ms), 1),
        "watcher_wakes_in_window": len(window),
        "watcher_longest_silence_ms": round(1e3 * max(
            (b[0] - a[0] for a, b in zip(window, window[1:])), default=0), 1)}
    with open(path, "w") as f:
        json.dump(dict(summary, odd=odd, longest=longest,
                       tick_ms=[round(m, 3) for m in ms]), f)
    print("ticks: %s" % json.dumps(dict(
        summary, odd=sorted(odd, key=lambda e: -e["ms"])[:4])), flush=True)
    print("longest: %s" % json.dumps(
        [[e["tick"], e["ms"], e["watcher_longest_silence_ms"]]
         for e in longest]), flush=True)
    for e in longest[:4]:
        for where in dict.fromkeys(e["stacks"]):    # each once, in order
            print("tick %d (%.1f ms), the loop stood %d times at: %s"
                  % (e["tick"], e["ms"], e["stacks"].count(where),
                     where[:900]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
