"""gia.device_s_per_dispatch.sweep: device seconds per run of the fused
GIA program, from the device trace's program line.  JAX names the program
after the jitted function, ``run`` in ``repro/opt/gia_jax.py``, so XLA
calls it ``jit_run``."""
PROGRAM = r"^jit_run(\(|$|\.)"


def read(view):
    if view.trace is None:
        return None
    runs, seconds = view.trace.program_time(PROGRAM)
    return seconds / runs if runs else None
