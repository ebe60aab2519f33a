"""Model FLOP/s utilisation: the operations the forward and backward passes
of one sample require (the configuration's ``model_flops``, from shapes, no
recomputation) x samples per second / (chips x the published bf16 peak).

Samples per second is the global batch over the MEDIAN interval between
step completions: a traced run's own rate is disturbed by the profiler
starting and stopping, its median step is not."""


def read(facts):
    step_s, peaks = facts.get("step_s_median"), facts.get("peaks")
    if not step_s or not peaks:
        return None
    per_sample = facts["model"].model_flops(facts["sizes"])
    rate = facts["global_batch"] / step_s
    return 100.0 * per_sample * rate / (
        facts["chips"] * peaks["bf16_flops_per_s"])
