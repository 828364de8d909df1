"""Decode lanes per fused device call over the window: the program's
``decode_tokens`` counter over its ``group_calls`` counter."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    if not a or not b:
        return None
    calls = b["group_calls"] - a["group_calls"]
    return (b["decode_tokens"] - a["decode_tokens"]) / calls if calls else None
