"""The decode megasteps' grouped expert matmuls against their roofline,
over the traced stretch.

Numerator: the least time for the routed experts of the decode
megasteps in the traced steps, the larger of FLOPs / peak and bytes /
peak bandwidth:
- megasteps = the distinct apps of each traced step, summed over the
  steps, times the configuration's expert layers;
- bytes = those megastep layers x (experts hit per expert-layer call x
  one expert's gate, up and down weights in bf16), plus the activations
  (each token-expert pair reads its row and writes its output row in
  each of the three matmuls);
- FLOPs = lanes x expert layers x experts per token x 3 matmuls x 2 x
  hidden size x expert width, with lanes summed over the traced steps.
Experts hit per expert-layer call is the window's mean, the growth of
``moe_decode_experts_hit`` over that of ``moe_decode_layer_calls``: an
approximation for the traced stretch, whose own counts the program
does not sync apart.

Denominator: the summed device time of the grouped-matmul events
(``%gmm``) that lie inside ``jit_chain_decode`` module events, read from
the traced run's profile."""
from benchmarks.chip import program_trace, xplane
from benchmarks.chip.arith import roofline_s

KERNEL = "%gmm"
DECODE = "jit_chain_decode"
_CACHE = {}


def gmm_decode_s(path: str) -> float:
    """Device time of ``%gmm`` events inside decode megasteps, within the
    traced stretch (first to last ``bench.*`` host span)."""
    if path in _CACHE:
        return _CACHE[path]
    planes = xplane.load(path)
    host = [(s, s + d) for evs in planes.get(xplane.HOST_PLANE, {}).values()
            for n, s, d in evs if n.startswith(xplane.HOST_SPAN_PREFIX)]
    lo, hi = min(a for a, _ in host), max(b for _, b in host)
    total, n_dev = 0.0, 0
    for name, lines in planes.items():
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        n_dev += 1
        mods = sorted((s, s + d) for n, s, d in
                      lines.get(program_trace.MODULES_LINE, [])
                      if program_trace.module_name(n) == DECODE
                      and s + d > lo and s < hi)
        ops = sorted((s, s + d) for n, s, d in lines.get(xplane.OPS_LINE, [])
                     if n.startswith(KERNEL))
        j = 0
        for a, b in mods:
            while j < len(ops) and ops[j][0] < a:
                j += 1
            k = j
            while k < len(ops) and ops[k][0] < b:
                total += max(0.0, min(ops[k][1], b, hi) - max(ops[k][0], lo))
                k += 1
    _CACHE[path] = total / n_dev if n_dev else 0.0
    return _CACHE[path]


def read(run):
    if run.peaks is None or not run.trace or not run.trace.get("file"):
        return None
    a0, b0 = run.counters.get("start"), run.counters.get("end")
    keys = ("moe_decode_experts_hit", "moe_decode_layer_calls")
    if not a0 or not b0 or any(k not in a0 or k not in b0 for k in keys):
        return None
    calls = b0[keys[1]] - a0[keys[1]]
    if not calls:
        return None
    hit = (b0[keys[0]] - a0[keys[0]]) / calls
    cfg = run.cell.cfg
    d, fe, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["num_experts_per_tok"])
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    a, b = run.trace["host_window"]
    megasteps = lanes = 0
    for s in run.steps:
        if s["t0"] >= a and s["t1"] <= b:
            megasteps += len({app for app, _, _ in s["lanes"]})
            lanes += len(s["lanes"])
    if not lanes:
        return None
    t = gmm_decode_s(run.trace["file"])
    if not t:
        return None
    flops = 2.0 * lanes * n_moe * k * 3 * d * fe
    nbytes = 2.0 * (megasteps * n_moe * hit * 3 * d * fe
                    + lanes * n_moe * k * 3 * (d + fe))
    least, _ = roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least / t
