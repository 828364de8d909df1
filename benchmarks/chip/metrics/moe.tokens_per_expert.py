"""Token-expert pairs per expert streamed in the decode megasteps over
the window: the growth of the program's ``moe_decode_pairs`` counter
over that of ``moe_decode_experts_hit`` (the experts given at least one
token, summed over megasteps and expert layers).  A dropless grouped
matmul reads only the experts it was given tokens for, so this is how
many tokens share each expert's weight read; 1 means every pair read an
expert of its own."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    keys = ("moe_decode_pairs", "moe_decode_experts_hit")
    if not a or not b or any(k not in a or k not in b for k in keys):
        return None
    hit = b[keys[1]] - a[keys[1]]
    return (b[keys[0]] - a[keys[0]]) / hit if hit else None
