"""host_step_ms: host time the serving loop spends on its own work per
decode step, in the traced stretch: the summed durations of the
program's host-work spans (`HOST_WORK`) that start inside the stretch,
over the number of decode enqueues (`kvnand.decode_enqueue`) among them.
The waits on the device (`kvnand.first_token_wait`, `kvnand.fetch`) are
left out.  A program without these spans reads None."""
HOST_WORK = ("kvnand.admit", "kvnand.prefill_enqueue",
             "kvnand.decode_enqueue", "kvnand.emit", "kvnand.route",
             "kvnand.commands")
STEP = "kvnand.decode_enqueue"


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.trace.t0, run.trace.t1
    spans = [e for e in run.trace.host()
             if e[2] in HOST_WORK and t0 <= e[3] < t1]
    steps = sum(1 for e in spans if e[2] == STEP)
    if not steps:
        return None
    return sum(e[4] for e in spans) / steps / 1e6
