"""HLO-level diff of the framework's fused train step vs the raw probe.

Round-4 located a ~9% residual (framework 103-107 ms vs raw 94.7 ms) and
XLA cost analysis put it at +1.5% flops / +2.3% bytes, but stopped there.
This tool goes one level down: it parses BOTH optimized HLO programs and
buckets every instruction by (opcode, normalized shape), then prints the
buckets where the two programs differ — the extra convolutions, fusions,
reductions, or copies the executor-generated program carries.

Usage:
    python benchmarks/hlo_diff.py            # lower+compile both, diff
    python benchmarks/hlo_diff.py --dump DIR # also write the HLO texts
"""
import os
import re
import sys
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def shape_nbytes(shape_str):
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


def _scan_shape(line, start):
    if start < len(line) and line[start] == "(":
        depth = 0
        for i in range(start, len(line)):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    return line[start:i + 1], i + 1
        return line[start:], len(line)
    m = re.match(r"\S+", line[start:])
    return (m.group(0), start + m.end()) if m else ("", start)


def inventory(hlo_text):
    """(opcode, result-shape) -> count over the WHOLE module, fusion
    bodies included.  Fusion-interior ops give finer granularity than
    fusion results, and the double counting (fusion + its body) is
    symmetric between the two programs being diffed."""
    counts = Counter()
    for line in hlo_text.splitlines():
        em = re.search(r"=\s*", line)
        if em is None:
            continue
        shape_s, end = _scan_shape(line, em.end())
        om = re.match(r"\s*([a-z][a-z0-9\-]*)", line[end:])
        if om is None:
            continue
        op = om.group(1)
        if op in ("parameter", "constant"):
            continue
        # strip layout annotations for stable bucketing
        shape_key = re.sub(r"\{[^}]*\}", "", shape_s)
        counts[(op, shape_key)] += 1
    return counts


def conv_inventory(hlo_text):
    """All convolution ops anywhere in the module (fusions included),
    keyed by result shape + window — the MXU work inventory."""
    counts = Counter()
    for line in hlo_text.splitlines():
        if " convolution(" not in line:
            continue
        em = re.search(r"=\s*", line)
        if em is None:
            continue
        shape_s, _ = _scan_shape(line, em.end())
        win = ""
        wm = re.search(r"window=\{([^}]*)\}", line)
        if wm:
            win = wm.group(1)
        dm = re.search(r"dim_labels=(\S+?)[,\s]", line)
        lbl = dm.group(1) if dm else ""
        counts[(re.sub(r"\{[^}]*\}", "", shape_s), win, lbl)] += 1
    return counts


def diff(name_a, inv_a, name_b, inv_b, weigh, top=40):
    keys = set(inv_a) | set(inv_b)
    rows = []
    for k in keys:
        ca, cb = inv_a.get(k, 0), inv_b.get(k, 0)
        if ca == cb:
            continue
        w = weigh(k)
        rows.append((abs(ca - cb) * w, k, ca, cb))
    rows.sort(reverse=True)
    print("== %s vs %s: %d differing buckets ==" % (name_a, name_b,
                                                    len(rows)), flush=True)
    for w, k, ca, cb in rows[:top]:
        print("  %-9s %s=%d %s=%d  %s" % (_fmt_bytes(w), name_a, ca,
                                          name_b, cb, k), flush=True)
    return rows


def _fmt_bytes(b):
    if b >= 1 << 20:
        return "%.1fMB" % (b / (1 << 20))
    if b >= 1 << 10:
        return "%.1fKB" % (b / (1 << 10))
    return "%dB" % b


def framework_hlo():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.models import resnet

    net = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (256, 3, 224, 224))],
             label_shapes=[("softmax_label", (256,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    ctx = mx.tpu()
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (256, 3, 224, 224)).astype(np.float32),
                 ctx=ctx)
    y = nd.array(rng.randint(0, 1000, (256,)).astype(np.float32), ctx=ctx)
    mod.forward_backward(DataBatch([x], [y]))
    mod.update()
    step = mod._fused_step
    fn = step._fn

    def aval(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)

    params = {n: aval(v) for n, v in step.params.items()}
    slots = {n: tuple(aval(s) for s in v) for n, v in step.slots.items()}
    aux = {n: aval(v) for n, v in step.aux.items()}
    data = {"data": aval(x.data), "softmax_label": aval(y.data)}
    lrs, wds, rescale, clip, extra = step._hyper_cache[5]
    from mxnet_tpu import random as _rnd
    rngk = _rnd.split_key()
    lowered = fn.lower(params, slots, aux, data, aval(lrs), aval(wds),
                       rescale, clip, aval(extra), aval(rngk))
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return compiled.as_text(), ca


def raw_hlo(layout="NCHW", bn="onepass"):
    """Run rn50_raw.py in a subprocess (its config is env+import-time) and
    collect the optimized HLO it dumps via COST=1 HLO_OUT=..."""
    import subprocess
    import tempfile

    path = os.path.join(os.path.dirname(__file__), "rn50_raw.py")
    fd, out = tempfile.mkstemp(suffix=".hlo")
    os.close(fd)
    env = dict(os.environ)
    env.update(LAYOUT=layout, BN=bn, COST="1", HLO_OUT=out)
    res = subprocess.run([sys.executable, path], env=env,
                         capture_output=True, text=True, timeout=1200)
    if res.returncode != 0:
        raise RuntimeError("rn50_raw failed:\n" + res.stderr[-2000:])
    ca = {}
    m = re.search(r"'flops': ([0-9.e+]+), 'bytes accessed': ([0-9.e+]+)",
                  res.stdout)
    if m:
        ca = {"flops": float(m.group(1)),
              "bytes accessed": float(m.group(2))}
    text = open(out).read()
    os.unlink(out)
    return text, ca


if __name__ == "__main__":
    dump = None
    if "--dump" in sys.argv:
        dump = sys.argv[sys.argv.index("--dump") + 1]
        os.makedirs(dump, exist_ok=True)

    # one process per chip: the child needs it, so it runs to its end
    # before this process first touches jax (framework_hlo) and holds it
    raw_text, raw_ca = raw_hlo()
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    fw_text, fw_ca = framework_hlo()

    if dump:
        open(os.path.join(dump, "framework.hlo"), "w").write(fw_text)
        open(os.path.join(dump, "raw.hlo"), "w").write(raw_text)

    print("cost: framework flops=%.4g bytes=%.4g | raw flops=%.4g "
          "bytes=%.4g" % (fw_ca.get("flops", 0),
                          fw_ca.get("bytes accessed", 0),
                          raw_ca.get("flops", 0),
                          raw_ca.get("bytes accessed", 0)), flush=True)

    print("\n-- convolution inventory (result shape, window, dims) --")
    diff("fw", conv_inventory(fw_text), "raw", conv_inventory(raw_text),
         weigh=lambda k: shape_nbytes(k[0]), top=60)

    print("\n-- whole-module op buckets (fusion bodies included) --")
    fw_inv = inventory(fw_text)
    raw_inv = inventory(raw_text)
    diff("fw", fw_inv, "raw", raw_inv,
         weigh=lambda k: shape_nbytes(k[1]), top=60)
