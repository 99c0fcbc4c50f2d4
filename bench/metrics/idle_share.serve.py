"""idle_share.serve: the share of the traced window in which no operation ran
on the device, in %, averaged over the chips used (device trace: 1 - the
union of operation intervals / the window).  Moves plan_p95_s."""


def read(view):
    if view.trace is None:
        return None
    share = view.trace.idle_share()
    return None if share is None else 100.0 * share
