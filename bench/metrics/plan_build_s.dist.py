"""Host seconds of `runtime.build_plan` for the sharded plan (the parts'
block stacks, the rank assignment and exchange routing, and handing
them to the chips without waiting): the one-chip reader `plan_build_s`,
which reads the driver's `plan_build` span."""
from bench.harness import load_reader

read = load_reader("plan_build_s")
