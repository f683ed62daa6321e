"""Forward+backward FLOPs of the traced steps from shapes (3 x forward, no
recomputation) over the device time of the program that took most of the
traced window (the compiled epoch program: forward/backward, update and
gossip together), as a share of the chips' bf16 peak."""

from chipbench.tracered import main_program_seconds
from chipbench.work import fwd_bwd_flops_per_step


def read(run):
    seconds = run["trace"] and main_program_seconds(run["trace"])
    if not seconds:
        return None
    tc = run["cell"]["train_config"]
    flops = run["traced_steps"] * fwd_bwd_flops_per_step(
        run["config"], tc["num_workers"], tc["batch_size"])
    return 100.0 * flops / seconds / (
        run["device"]["count"] * run["peaks"]["bf16_flops"])
