"""KV pages reserved over pages holding cached tokens, from the
program's own counters over the window: the growth of
``kv_page_steps_reserved`` (pool pages in use, summed over steps) over
that of ``kv_page_steps_live`` (each resident request's cached tokens in
whole pages on each attention hop, summed over steps)."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    keys = ("kv_page_steps_reserved", "kv_page_steps_live")
    if not a or not b or any(k not in a or k not in b for k in keys):
        return None
    live = b[keys[1]] - a[keys[1]]
    return (b[keys[0]] - a[keys[0]]) / live if live else None
