"""The KDA decode kernel's share of its roofline: the bytes the traced
window's decode steps must move through it — every ACTIVE row's float32
state read and written once, in each KDA layer — / (the summed device
seconds of the kernel's events x the peak bytes/s), %.  Decode steps in
the traced window and rows a step are counted as
``serve.kda_decode_hbm_share`` counts them.  A row that names no request
also moves the trash slot's state through the kernel; it is not counted,
so the share cannot pass 100.  None where the trace holds no such kernel
(a program that steps the state in plain XLA)."""
from families import solar_open2
from harness import readers, xplane

# the kernel's events: its custom calls, named by the program
KERNEL = r"^kda_decode(\.\d+)?:"


def read(ctx):
    tr = ctx.get("trace")
    t = readers.bucket_totals(ctx, "decode")
    if not tr or not t or not t[0]:
        return None
    t_kernel = xplane.kernel_time(tr["custom_calls"], KERNEL)
    if t_kernel <= 0:
        return None
    steps, _, active_rows, _ = t
    steps_traced = tr["window_s"] * steps / ctx["window_s"]
    z = solar_open2.reference.sizes(ctx["config"])
    layers = solar_open2.params(ctx["config"])["kda_layers"]
    state = 2 * active_rows / steps * z["kh"] * z["kd"] * z["kd"] * 4
    need = steps_traced * layers * state
    return 100.0 * need / (t_kernel * ctx["peaks"]["hbm_bytes_per_s"])
