"""compiles_in_window: ``executor.device_call`` spans with
``stage=compile`` in the window: shapes the warm-up missed."""


def read(ctx):
    return sum(1 for s in ctx.spans if s.name == "executor.device_call"
               and s.labels.get("stage") == "compile")
