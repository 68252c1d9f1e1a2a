"""Host seconds of ``GNNEngine.build``: partition and aggregation plan."""


def read(ctx):
    return ctx["plan_build_s"]
