"""hbm_peak_gb (GB): the compiler's peak device memory of the step program,
``compiled.memory_analysis().peak_memory_in_bytes`` (per device), in 1e9
bytes.  This headroom decides which plans and batches fit."""


def read(ctx):
    return ctx.hbm_peak_bytes / 1e9
