"""memory_stats()["peak_bytes_in_use"] of the fullest device over the HBM
bytes of peaks.json, in percent."""


def compute(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.peaks["hbm_bytes"]
