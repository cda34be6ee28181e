"""Device time of the frontier's segment program (XLA modules
``jit_segment``, from the trace) per lane instruction it executed
(``frontier.device_instructions``), both over the window, in ns."""


def read(run):
    if run.trace is None:
        return None
    instr = run.counter("frontier.device_instructions")
    if not instr or not run.trace["program_s"]:
        return None
    return 1e9 * run.trace["program_s"] / instr
