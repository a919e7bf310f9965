"""The paged flash-decode kernel's share of its roofline over the traced
window: the least time the chip could take for the kernel's work (the
larger of its ops over the bf16 peak and its bytes over HBM bandwidth,
``counting.flash_decode_cost`` over every decode step's live slots) over
the kernel's device time.  The kernel runs where the tuner chose it
(``impl: flash``); the trace names it after its jitted entry,
``flash_decode``.  Where the decode attention runs another impl, there is
nothing to read."""

from bench import counting

KERNEL = "flash_decode"


def kernels(run):
    return (KERNEL,) if run.attn.get("impl") == "flash" else ()


def read(run):
    if run.trace is None or run.peaks is None or not kernels(run):
        return None
    ops = nbytes = 0
    for rec in run.clients.decodes:
        if rec.in_window:
            o, b = counting.flash_decode_cost(
                run.dims, [pos + 1 for _, _, pos in rec.rows], run.page)
            ops += o
            nbytes += b
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / run.trace["kernel_s"][KERNEL]
