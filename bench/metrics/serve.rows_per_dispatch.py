"""serve.rows_per_dispatch: mean rows per fused dispatch of the measured
PlanServer, from its ``stats()["mean_batch"]`` (the ``batch_rows``
histogram)."""


def read(view):
    return view.counters.get("mean_batch") or None
