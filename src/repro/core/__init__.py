# The paper's primary contribution — implement the SYSTEM here
# (scheduler, optimizer, data path, serving loop, etc.) in the
# host framework. Add sibling subpackages for substrates.
#
# Public typed GAS runtime surface (see core/runtime.py):
from .batch import BlockStructure, GASBatch                      # noqa: F401
from .history import HistoryStore                                # noqa: F401
from .runtime import (GASConfig, GASPlan, GASState, build_plan,  # noqa: F401
                      evaluate_exact, fit, init_state, make_step_fn,
                      predict, train_epoch, train_step)
# Shared execution-config base (see core/config.py): the backend /
# history_dtype / staleness knobs GASConfig and ServeConfig both inherit.
from .config import HistoryExecConfig                            # noqa: F401
# Serving surface (see core/serve.py): history tables as a warm
# node-embedding cache behind the plan/state/step contract
# (ServeConfig -> build_serve_plan -> init_serve_state -> serve_request).
# The deprecated `serve()` shim itself is NOT re-exported — the bare name
# would shadow the `core.serve` submodule attribute (`from repro.core
# import serve as S` must keep returning the module); call it as
# `serve.serve(...)` (or, better, `serve_request`).
from .serve import (ServeConfig, ServePlan, ServeState,          # noqa: F401
                    apply_feature_update, bind_state,
                    build_serve_plan, init_serve_state,
                    make_serve_step_fn, serve_request, serve_step,
                    stale_closure)
# Serving process split (see core/serve_service.py): a history-owning
# backend + stateless frontends over a versioned pull/push wire protocol.
from .serve_service import (HistoryBackend, InProcTransport,     # noqa: F401
                            ServeFrontend, SocketTransport,
                            serve_backend_forever)
# Evolving-graph surface (see core/delta.py, core/dynamic.py): typed
# graph deltas with CSR patch application, and the snapshot-sequence
# trainer whose `advance` repairs partition/batches/histories
# incrementally. The `delta`/`dynamic` submodule attributes are not
# shadowed — only distinct class/function names are lifted.
from .delta import (GraphDelta, apply_delta, hop_closure,        # noqa: F401
                    out_closure, random_delta)
from .dynamic import (AdvanceInfo, DynamicGASConfig, advance,    # noqa: F401
                      build_dynamic_plan, fit_dynamic)
