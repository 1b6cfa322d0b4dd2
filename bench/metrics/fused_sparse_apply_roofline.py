"""The replay kernel's share of its roofline, in percent: the memory
traffic that replaying differentials through Adam cannot avoid
(``benchlib.flops.replay_min_bytes``: params and both moments read and
written once per differential, plus the payload) at the chip's HBM
peak, over the device time of the kernel's ops in the trace. The kernel
is ``kernels/replay.topk_apply``, entered through
``kernels.ops.fused_sparse_apply``, whose name its ops carry. Bound by
bandwidth, not FLOPs."""
from benchlib.flops import replay_min_bytes
from benchlib.tracing import short_name

KERNEL = "fused_sparse_apply"


def read(run):
    t = run.trace_summary
    if run.mode != "resume" or t is None or run.peaks is None:
        return None
    secs = sum(v for k, v in t["op_s"].items()
               if short_name(k).split(".")[0] == KERNEL)
    if secs <= 0 or not run.replayed:
        return None
    state_bytes = 12 * run.n_params
    need = sum(replay_min_bytes(state_bytes, run.payload_bytes, n)
               for n in run.replayed)
    return 100.0 * need / run.peaks.hbm_bw / secs
