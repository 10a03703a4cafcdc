"""Share of its roofline that the GBDT kernel reaches: the least time the
chip could take for the scoring problems of the window (``work.py``:
clients x candidates x trees x (depth + 1) operations; client rows,
candidate grid and model tables read once) over the kernel's device
time in the trace."""
from chipbench import work

KERNEL = "gbdt_logits_pallas"


def read(run):
    if run.trace is None or not run.scorer_rows or not run.models:
        return None
    kernel_s = run.trace.op_time(KERNEL)
    if kernel_s <= 0.0:
        return None
    c = run.n_candidates
    least = 0.0
    for op, rows in run.scorer_rows:
        m = run.models[op]
        n = rows // c
        t, d = m["n_trees"], m["depth"]
        least += work.min_seconds(
            work.gbdt_ops(n, c, t, d),
            work.gbdt_bytes(n, c, t, d, m["n_features"] - run.n_theta,
                            run.n_theta), run.peaks)
    return least / kernel_s * 100.0
