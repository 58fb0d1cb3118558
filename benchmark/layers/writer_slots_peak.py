"""Front door + pipeline stages, stage deli, the writer slots: the most
write slots any document held at once (the sequencer's
``writer_slots_peak``), as the traffic kind read it off the pipeline's
counts after the window. Not a delta: the joins come before the window."""


def read(ctx):
    return ctx.result.get("layer", {}).get("writer_slots_peak")
