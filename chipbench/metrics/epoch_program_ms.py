"""Device time per step of the program that took most of the traced window
(the compiled epoch program), from the trace's ``XLA Modules`` row: what
``step_ms`` would be with no input staging and no host in it."""

from chipbench.tracered import main_program_seconds


def read(run):
    seconds = run["trace"] and main_program_seconds(run["trace"])
    return 1e3 * seconds / run["traced_steps"] if seconds else None
