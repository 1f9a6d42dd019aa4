"""``fold_update``'s share of its memory roofline: the bytes its calls
must move (computed from the shapes by ``harness.kernel_bytes``) at the
chip's peak HBM bandwidth, over the kernel's time in the trace."""

KERNEL = "fold_update"


def read(rec):
    tr = rec["trace"]
    per_call = rec.get("kernel_bytes", {}).get(KERNEL)
    if tr is None or not per_call:
        return None
    calls, seconds = tr.kernel(KERNEL)
    if not calls or seconds <= 0:
        return None
    return 100.0 * calls * per_call / rec["peaks"]["hbm_bytes_per_s"] / seconds
