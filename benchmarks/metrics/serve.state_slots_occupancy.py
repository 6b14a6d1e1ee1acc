"""Recurrent-state slots in use at each decode step, summed, / slots x
steps, % (the engine's ``state_slots`` / ``state_slot_steps``)."""


def read(ctx):
    s = ctx.get("summary") or {}
    if not s.get("state_slot_steps"):
        return None
    return 100.0 * s["state_slots"] / s["state_slot_steps"]
