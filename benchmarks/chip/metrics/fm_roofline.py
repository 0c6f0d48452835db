"""Share of its roofline that the FM program (``fm_refine_multi``, the
chip's XLA path of ``core/fm.py``) reaches in the traced tail: the
least time its launches need at the chip's HBM bandwidth
(``kernel_bytes.fm_least_bytes``), over the device time of its
programs in the trace."""
from kernel_bytes import FM_BOUND, fm_least_bytes


def read(run):
    if run.trace is None:
        return None
    from devtrace import module_seconds
    device_s = module_seconds(run.trace, "fm_refine_multi")
    launches = [p for _, kind, p in run.trace_events
                if kind == "launch" and p["kind"] == "fm"]
    if device_s <= 0 or not launches:
        return None
    least = sum(fm_least_bytes(p["lanes_pad"], *p["bucket"][:2])
                for p in launches)
    return 100.0 * least / run.peaks[FM_BOUND] / device_s
