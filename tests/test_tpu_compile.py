"""Compile the Pallas kernels for a TPU v5e chip that is described, not
attached: Mosaic refuses layouts (unaligned slices, one-row blocks) that
the interpreter runs, so interpret-mode tests alone cannot show that a
kernel runs on the chip. Nothing executes here; each case lowers and
compiles one kernel at the widths of `chip_smoke.py` (GCN 3 x 256 on a
10k-node graph: ~1k nodes and ~1.5k halo rows per batch, forward blocks
[9, 20, 128, 128]; an SLO=0 serving refresh of the whole graph,
[64, 80, 128, 128]). `fused.gather_spmm` is also compiled at the
benchmark cells' shape (forward blocks [17, 80, 128, 128], 2,176 batch
and 8,107 halo rows of a 100,001-row table), where its VMEM panel is
largest, and for a refresh whose halo outgrows the panel, which takes
the per-block path. `scatter.scatter_rows` is compiled at the cells'
shape too (2,176 rows pushed into the 100,001-row table, f32 and int8),
and checked to push in place into a donated table. The vq form of
`fused.gather_spmm` is left out: it does not compile, and
`ops.gas_aggregate` refuses it on `pallas`.

The sharded epoch of `GASConfig(ranks=4)` (`dist_gas.make_epoch_fn`:
`shard_map`, halo exchange by `ppermute`, the kernels inside) is compiled
whole for the four chips of the described host, at the cell's widths on
a small graph, with f32 and int8 histories.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D, M = 10_001, 256, 1_536            # history rows, width, pulled rows
R, K, BN = 9, 20, 128                   # forward block grid
N_IN = 1_032                            # in-batch rows
R_SERVE, K_SERVE = 64, 80               # SLO=0 refresh of the whole graph
M_SERVE = K_SERVE * BN - R_SERVE * BN - 1   # its halo rows: 80 column blocks
M_LARGE = 64 * 1024 - R_SERVE * BN - 1  # a halo of 512 column blocks
N_CELL, R_CELL, N_IN_CELL, M_CELL = 100_001, 17, 2_176, 8_107  # the cells
HEADS, FP = 8, 128                      # GAT heads, lane-padded head width
S, C, DS = 32, 256, 8                   # vq subvectors, entries, sub-width
f32, i32, i8, u8 = jnp.float32, jnp.int32, jnp.int8, jnp.uint8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _cases():
    from repro.kernels import (bcsr_spmm, edge_softmax as esk, fused,
                               gather, pna_reduce as pnk, scatter)
    blocks = [((R, K, BN, BN), f32), ((R, K), i32)]

    def halo(m):                                        # nodes, mask
        return [((m,), i32), ((m,), jnp.bool_)]

    serve = [((R_SERVE * BN, D), f32), ((N, D), i8),
             ((R_SERVE, K_SERVE, BN, BN), f32), ((R_SERVE, K_SERVE), i32)]
    cell = [((R_CELL, K, BN, BN), f32), ((R_CELL, K), i32)]
    kw = dict(interpret=False)
    return {
        "bcsr_spmm": (functools.partial(bcsr_spmm.bcsr_spmm, **kw),
                      [((K * BN, D), f32)] + blocks),
        "gather_rows": (functools.partial(gather.gather_rows, **kw),
                        [((N, D), f32), ((M,), i32)]),
        "gather_rows_dq": (
            lambda t, s, i: gather.gather_rows(t, i, s, **kw),
            [((N, D), i8), ((N,), f32), ((M,), i32)]),
        "gather_rows_vq": (
            functools.partial(gather.gather_rows_vq, **kw),
            [((N, S), u8), ((S, C, DS), f32), ((N,), f32), ((M,), i32)]),
        "scatter_rows": (functools.partial(scatter.scatter_rows, **kw),
                         [((N, D), f32), ((M,), i32), ((M, D), f32)]),
        "scatter_rows_vq": (
            functools.partial(scatter.scatter_rows_vq, **kw),
            [((N, S), u8), ((M,), i32), ((M, D), f32), ((M,), f32),
             ((S, C, DS), f32)]),
        "scatter_rows_q": (
            functools.partial(scatter.scatter_rows, **kw),
            [((N, D), i8), ((M,), i32), ((M, D), f32), ((M,), f32)]),
        # the cells' push: a padded part into the partial-tiled table
        "scatter_rows_cell_f32": (
            functools.partial(scatter.scatter_rows, **kw),
            [((N_CELL, D), f32), ((N_IN_CELL,), i32),
             ((N_IN_CELL, D), f32)]),
        "scatter_rows_cell_int8": (
            functools.partial(scatter.scatter_rows, **kw),
            [((N_CELL, D), i8), ((N_IN_CELL,), i32),
             ((N_IN_CELL, D), f32), ((N_IN_CELL,), f32)]),
        "gather_spmm_f32": (
            functools.partial(fused.gather_spmm, **kw),
            [((N_IN, D), f32), ((N, D), f32)] + blocks + halo(M)),
        "gather_spmm_int8": (
            functools.partial(fused.gather_spmm, **kw),
            [((N_IN, D), f32), ((N, D), i8)] + blocks + halo(M)
            + [((N,), f32)]),
        # 80 column blocks: a 10.5 MB panel, its [80, bn] plan whole in SMEM
        "gather_spmm_refresh": (
            functools.partial(fused.gather_spmm, **kw),
            serve + halo(M_SERVE) + [((N,), f32)]),
        # 512 column blocks outgrow the panel: the per-block path, whose
        # [R, K, bn] plan would outgrow SMEM as whole arrays
        "gather_spmm_refresh_per_block": (
            functools.partial(fused.gather_spmm, **kw),
            serve + halo(M_LARGE) + [((N,), f32)]),
        "gather_spmm_cell_f32": (
            functools.partial(fused.gather_spmm, **kw),
            [((N_IN_CELL, D), f32), ((N_CELL, D), f32)] + cell
            + halo(M_CELL)),
        "gather_spmm_cell_int8": (
            functools.partial(fused.gather_spmm, **kw),
            [((N_IN_CELL, D), f32), ((N_CELL, D), i8)] + cell
            + halo(M_CELL) + [((N_CELL,), f32)]),
        "edge_softmax_fwd": (
            functools.partial(esk.edge_softmax_fwd, **kw),
            [((HEADS, R * BN), f32), ((HEADS, K * BN), f32),
             ((HEADS, K * BN, FP), f32)] + blocks),
        "edge_softmax_bwd_row": (
            functools.partial(esk.edge_softmax_bwd_row, **kw),
            [((HEADS, R * BN), f32), ((HEADS, K * BN), f32),
             ((HEADS, K * BN, FP), f32), ((HEADS, R * BN, FP), f32)]
            + [((HEADS, R * BN), f32)] * 3 + blocks),
        "edge_softmax_bwd_col": (
            functools.partial(esk.edge_softmax_bwd_col, **kw),
            [((HEADS, R * BN), f32), ((HEADS, R * BN), f32),
             ((HEADS, R * BN, FP), f32), ((HEADS, R * BN, FP), f32)]
            + [((HEADS, R * BN), f32)] * 3 + blocks),
        "pna_reduce_fwd": (
            functools.partial(pnk.pna_reduce_fwd, **kw),
            [((R * BN, D), f32), ((K * BN, D), f32)] + blocks),
        "pna_reduce_bwd_row": (
            functools.partial(pnk.pna_reduce_bwd_row, **kw),
            [((R * BN, D), f32), ((K * BN, D), f32)]
            + [((R * BN, D), f32)] * 7 + blocks),
        "pna_reduce_bwd_col": (
            functools.partial(pnk.pna_reduce_bwd_col, **kw),
            [((R * BN, D), f32), ((R * BN, D), f32)]
            + [((R * BN, D), f32)] * 7 + blocks),
    }


KERNELS = ["bcsr_spmm", "gather_rows", "gather_rows_dq", "gather_rows_vq",
           "scatter_rows", "scatter_rows_q", "scatter_rows_vq",
           "scatter_rows_cell_f32", "scatter_rows_cell_int8",
           "gather_spmm_f32", "gather_spmm_int8", "gather_spmm_refresh",
           "gather_spmm_refresh_per_block", "gather_spmm_cell_f32",
           "gather_spmm_cell_int8",
           "edge_softmax_fwd", "edge_softmax_bwd_row",
           "edge_softmax_bwd_col", "pna_reduce_fwd", "pna_reduce_bwd_row",
           "pna_reduce_bwd_col"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,width", [
    ("gather_spmm_f32", D), ("gather_spmm_int8", D),
    ("gather_spmm_refresh", D), ("gather_spmm_refresh_per_block", None),
    ("gather_spmm_cell_f32", D), ("gather_spmm_cell_int8", D)])
def test_gather_spmm_staging_path(name, width):
    """Which staging path each compiled case takes (`fused.panel_width`,
    shapes alone): the whole-width panel, or the per-block fallback."""
    from repro.kernels import fused
    x_in, table, vals, _, halo_nodes = [jax.ShapeDtypeStruct(s, dt) for
                                        s, dt in _cases()[name][1][:5]]
    assert fused.panel_width(x_in, table, vals, halo_nodes,
                             bd=BN) == width


@pytest.mark.parametrize("name", ["scatter_rows_cell_f32",
                                  "scatter_rows_cell_int8"])
def test_scatter_rows_pushes_in_place(name, one_chip):
    """With the table donated, the compiled push writes into it: the
    output aliases the table, and no temporary holds a copy of it."""
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    mem = jax.jit(fn, donate_argnums=0).lower(*args).compile() \
        .memory_analysis()
    table = args[0].size * args[0].dtype.itemsize    # before tile padding
    assert mem.alias_size_in_bytes >= table
    assert mem.temp_size_in_bytes < table // 8


def _sharded_epoch_args(topo, history_dtype):
    """The un-jitted sharded epoch and abstract arguments for it on a mesh
    of the described host's four chips: GCN 128 -> 256 -> 256 -> 40, a
    1,000-node graph in 8 parts (2 supersteps)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import dist_gas, gas, history, runtime
    from repro.core.partition import random_partition
    from repro.data.graphs import citation_graph
    from repro.gnn.model import GNNSpec, init_gnn
    from repro.train.optimizer import adamw_init

    g = citation_graph(num_nodes=1000, num_features=128, num_classes=40,
                       seed=1)
    part = random_partition(g.num_nodes, 8, seed=0)
    host, _, _ = dist_gas.build_dist(
        part, gas.build_batches(g, part, build_blocks=True), 4)
    mesh = Mesh(np.array(topo.devices[:4]), (dist_gas.AXIS,))
    shard = NamedSharding(mesh, P(dist_gas.AXIS))
    rep = NamedSharding(mesh, P())
    spec = GNNSpec("gcn", 128, 256, 40, 3)

    def place(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    def state():
        params = init_gnn(jax.random.key(0), spec)
        store = history.HistoryStore.create(
            4 * (host.rows + 1), spec.hist_dims(), backend="pallas",
            history_dtype=history_dtype)
        return runtime.GASState(params, adamw_init(params), store,
                                jax.random.key(1))

    st = jax.eval_shape(state)
    st = runtime.GASState(place(st.params, rep), place(st.opt_state, rep),
                          place(st.histories, shard), place(st.rng, rep))
    n = g.num_nodes
    rest = [jax.ShapeDtypeStruct(s, dt, sharding=rep) for s, dt in
            (((8,), i32), ((n, 128), f32), ((n + 1,), i32),
             ((n + 1,), jnp.bool_))]
    cfg = runtime.GASConfig(num_parts=8, ranks=4, backend="pallas",
                            history_dtype=history_dtype)
    fn = dist_gas.make_epoch_fn(spec, cfg, "pallas", mesh)
    return fn, [st, place(host, shard)] + rest


@pytest.mark.parametrize("history_dtype", ["f32", "int8"])
def test_sharded_epoch_compiles_for_four_v5e_chips(history_dtype, topo):
    fn, args = _sharded_epoch_args(topo, history_dtype)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text and "all-reduce" in text
