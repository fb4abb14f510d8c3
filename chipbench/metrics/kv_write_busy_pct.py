"""The KV pool's write program (`_append_slots`, run on every decode step
and every prefill chunk): its device time as a share (%) of the device's
busy time in the traced window. None where no such program ran."""

KV_WRITE_PROGRAM = r"_append_slots_impl"


def reduce(ctx):
    if ctx.trace is None:
        return None
    seconds, calls = ctx.trace.module_time(KV_WRITE_PROGRAM)
    return 100.0 * seconds / ctx.trace.busy_s if calls else None
