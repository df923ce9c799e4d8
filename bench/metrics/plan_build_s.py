"""Host seconds of `runtime.build_plan`: `gas.build_batches` filling the
batches and BCSR blocks on the host, and handing them to the device
without waiting for the transfer (the harness times that wait apart, as
its `upload` span); a part of `setup_s`."""


def read(ctx):
    return ctx["spans"].get("plan_build")
