"""The Mamba-2 layers' decode traffic against the memory's peak: the
bytes the traced window's decode steps MUST move for those layers (every
active row's state read and written, its convolution tail, the mixers'
weights once a step: ``families/granite4h.ssm_decode_bytes``, each byte
counted once) / (the traced seconds of the decode programs' operations
under the ``ssm`` scope x the peak bytes/s), %.  Decode steps in the
traced window = the window's rate of steps x the trace's length; rows a
step = the window's mean."""
from families import granite4h
from harness import readers


def read(ctx):
    parts = granite4h.part_seconds(ctx, kinds=("decode",))
    t = readers.bucket_totals(ctx, "decode")
    if not parts or not parts.get("ssm") or not t or not t[0]:
        return None
    steps, _, active_rows, _ = t
    steps_traced = ctx["trace"]["window_s"] * steps / ctx["window_s"]
    need = steps_traced * granite4h.ssm_decode_bytes(
        ctx["config"], active_rows / steps)
    return 100.0 * need / (parts["ssm"] * ctx["peaks"]["hbm_bytes_per_s"])
