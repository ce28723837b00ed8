"""Whole train step: samples/s times the forward and backward ops per sample
(``work.lut_stack_train_ops``) over the cell's chips times the bf16 peak, in %."""


def read(run):
    rate = run.e2e.get("train_samples_per_s")
    if not rate:
        return None
    return (100.0 * rate * run.work["ops_per_sample"]
            / (run.chips * run.peak["bf16_flops_per_s"]))
