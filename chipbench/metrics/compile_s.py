"""Host seconds of ``lower().compile()`` of the training step (a hit in
the persistent compile cache reads short)."""


def read(ctx):
    return ctx["compile_s"]
