"""Least time the chip needs to push each tick's live rows through the
int8-weight projections (``work.weight_matmuls``, each matrix read once per
tick), over the device time of the ``wq_matmul`` kernel's events."""
import work


def read(run):
    spent = (run.trace or {}).get("kernel_s", {}).get("wq_matmul", 0.0)
    if spent <= 0:
        return None
    pub = run.cfg["published"]
    layers = pub["num_hidden_layers"]
    need = 0.0
    for rows in run.rows:
        for shape in run.adapter.layer_matmuls(pub):
            f, b = work.weight_matmuls(len(rows), [shape], layers)
            need += work.least_time(f, b, run.peak)
    return 100.0 * need / spent
