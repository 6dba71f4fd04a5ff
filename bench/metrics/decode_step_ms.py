"""Model step: device milliseconds per call of the replica's greedy decode
step program (``Replica._step_impl`` / ``_step_paged_impl``: the whole
``model.decode_step`` over every lane and the on-device argmax), from the
trace's program events."""
PROGRAM = r"_step(_paged)?_impl"


def read(name, run):
    if run.trace is None:
        return None
    secs, calls = run.trace.module_seconds_calls(PROGRAM)
    return secs / calls * 1e3 if calls else None
