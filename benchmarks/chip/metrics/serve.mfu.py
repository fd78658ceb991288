"""Model FLOPs of every prefilled and decoded token of the window over the
wall time spent inside ServeEngine.serve calls times the chip's bf16 peak.
Waiting for arrivals is left out, so the share moves with speed and not
with the offered rate."""


def read(run):
    c = run.counters
    if not c.get("serve_wall_s"):
        return None
    return 100.0 * c["model_flops"] / (c["serve_wall_s"]
                                       * run.peaks["bf16_flops_per_s"])
