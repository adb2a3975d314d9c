"""Least time the chip needs to push each tick's live rows through the
hybrid's int8-weight projections, each shape counted over the layers that
hold it (``adapter.wq_matmul_shapes``: Mamba in/x/out projections, the
attention projections, the MLP of every layer; each matrix read once per
tick), over the device time of the ``wq_matmul`` kernel's events."""
import work


def read(run):
    spent = (run.trace or {}).get("kernel_s", {}).get("wq_matmul", 0.0)
    shapes = getattr(run.adapter, "wq_matmul_shapes", None)
    if spent <= 0 or shapes is None or not run.rows:
        return None
    held = shapes(run.cfg["published"])
    need = 0.0
    for rows in run.rows:
        for shape, layers in held:
            f, b = work.weight_matmuls(len(rows), [shape], layers)
            need += work.least_time(f, b, run.peak)
    return 100.0 * need / spent
