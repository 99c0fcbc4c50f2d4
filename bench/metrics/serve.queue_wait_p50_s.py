"""serve.queue_wait_p50_s: median seconds a request waited in the
PlanServer's queue before its micro-batch was taken, from the measured
server's own ``stats()["queue_wait_s"]``."""


def read(view):
    return view.counters.get("queue_wait_p50_s")
