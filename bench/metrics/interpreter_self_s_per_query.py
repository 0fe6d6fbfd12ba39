"""Host interpreter time per query that is not spent waiting on the
device: for each ``run`` span that started in the window, its duration
less that of the ``host_sync`` spans under it, summed, over the queries
the runs answered (their ``batch_size``). A program without ``host_sync``
spans reads nothing."""


def read(window):
    runs = {s.span_id: s for s in window.spans
            if s.name == "run" and window.t0 <= s.t_start < window.t1}
    syncs = [s for s in window.spans if s.name == "host_sync"]
    if not runs or not syncs:
        return None
    parent = {s.span_id: s.parent_id for s in window.spans}
    waited = dict.fromkeys(runs, 0.0)
    for s in syncs:
        up = s.parent_id
        while up is not None and up not in runs:
            up = parent.get(up)
        if up is not None:
            waited[up] += s.duration_s
    queries = sum(int(r.attrs.get("batch_size", 1)) for r in runs.values())
    return sum(r.duration_s - waited[i] for i, r in runs.items()) / queries
