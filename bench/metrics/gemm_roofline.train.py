"""The step's GEMMs against their roofline: the least time the chip could
take for the GEMMs one step requires (each bounded by its operations over
the bf16 peak or its bytes over HBM bandwidth), over the device time of the
step's matrix-multiplying ops in the trace (convolution and dot ops, the
fusions that hold one, and the repository's Pallas matmul and attention
kernels), per step."""


def read(run):
    k, t = run.counters, run.trace_data
    if k.get("kind") != "train" or t is None or run.peaks is None:
        return None
    runs = t.module_runs(k["step_module"])
    if not runs:
        return None
    mm = t.op_time_s(k["matmul_ops"], runs)
    if mm <= 0:
        return None
    return 100.0 * k["gemm_roofline_s_per_step"] * len(runs) / mm
