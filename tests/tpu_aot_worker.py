"""AOT-compile the repo's Pallas kernels, and the packed FM step, for a
device-less TPU v5e topology.

Run as a subprocess by tests/test_tpu_aot_compile.py (libtpu takes a lock
and is noisy): ``python tests/tpu_aot_worker.py CASE...`` prints one JSON
line per case. Every kernel is lowered with ``interpret=False`` at bench
geometry and handed to the real Mosaic/XLA TPU compiler, so a kernel that
only ever ran in interpret mode and does not fit VMEM fails HERE, without
chip time.
"""

import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from hivemall_tpu.ops import fm, fm_pallas, pallas_hist, rows_pallas
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer

# the kernels under test ask the device policy; here the target is the
# topology, not this process's (CPU) backend
pallas_hist.pallas_interpret = lambda: False
rows_pallas.use_kernels_default = lambda: True

TOPO = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
DEV0 = SingleDeviceSharding(TOPO.devices[0])

F, K, MRF, HP = 40, 4, 8192, 2          # flagship geometry (chip_smoke FULL)
LAMS = (0.01, 0.01, 0.01)


def _sds(shape, dtype, sharding=DEV0):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _parts_state(sh_t=DEV0, sh_0=DEV0):
    rows = F * MRF * HP
    params = {"T2": _sds((rows, 128), jnp.bfloat16, sh_t),
              "w0": _sds((), jnp.float32, sh_0)}
    opt = {"T2": {"gg": _sds((rows, 128), jnp.float32, sh_t)},
           "w0": {"gg": _sds((), jnp.float32, sh_0)}}
    return params, opt


def parts_step():
    B = 32768
    step = fm_pallas.make_parts_step(
        get_loss("logloss"), lambda t: 0.1, LAMS, F, K, MRF, unit_val=True,
        interpret=False)
    params, opt = _parts_state()
    step.lower(params, opt, _sds((), jnp.float32),
               _sds((B, F), jnp.int32), _sds((B,), jnp.float32),
               _sds((B,), jnp.float32)).compile()


def _mesh_2x2():
    return Mesh(np.asarray(TOPO.devices).reshape(2, 2), ("dp", "tp"))


def _mesh_1x4():
    return Mesh(np.asarray(TOPO.devices).reshape(1, 4), ("dp", "tp"))


def parts_accum_kernel_2x2():
    """The sharded step's accumulate kernel at its per-rank flagship
    shapes (B=32768 over dp=2, F=40 over tp=2), one instance per device."""
    mesh = _mesh_2x2()
    Bd, Fl, chunk = 32768 // 2, F // 2, 2048
    kern = fm_pallas._make_scatter_accum_kernel(Bd, Fl, Fl, MRF, HP, chunk,
                                                interpret=False)
    every = P(("dp", "tp"))
    fn = jax.jit(jax.shard_map(kern, mesh=mesh, in_specs=(every, every),
                               out_specs=every, check_vma=False))
    sh = NamedSharding(mesh, every)
    fn.lower(_sds((4 * Fl, Bd // 128, 128), jnp.int32, sh),
             _sds((4 * Fl, Bd * HP // 16, 16, 128), jnp.bfloat16, sh)
             ).compile()


def parts_step_sharded():
    B = 32768
    mesh = _mesh_2x2()
    step = fm_pallas.make_parts_step_sharded(
        get_loss("logloss"), lambda t: 0.1, LAMS, F, K, MRF, mesh,
        unit_val=True, interpret=False)

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))
    params, opt = _parts_state(ns("tp", None), ns())
    step.lower(params, opt, _sds((), jnp.float32, ns()),
               _sds((B, F), jnp.int32, ns("dp", None)),
               _sds((B,), jnp.float32, ns("dp")),
               _sds((B,), jnp.float32, ns("dp"))).compile()


def _slab_gathers(text, slab):
    """{branch of the gather's `cond`: shape of the operand read} for the
    step's forward gathers (`hm.gather`, a result of shape `slab`)."""
    out = {}
    for m in re.finditer(r"= %s\S* gather\(%%([\w.]+),.*?op_name=\"([^\"]*)\""
                         % re.escape(slab), text):
        operand, path = m.groups()
        if "/hm.gather/" not in path:
            continue
        branch = re.search(r"branch_\d", path)
        out[branch.group(0) if branch else "no_cond"] = re.search(
            r"%%%s = (\w+\[[\d,]*\])" % re.escape(operand), text).group(1)
    return out


def _compact_table_spaces(text, shape):
    """The memory spaces the compiled text gives the compact table in the
    fill's loop (`S(1)`: the chip's fast memory; "" is HBM)."""
    loop = re.search(r" while\((?:(?!\n).)*?/hm\.gather/while", text)
    line = text[text.rindex("\n", 0, loop.start()):loop.end()]
    return set(re.findall(r"%s\{[^}]*?(S\(\d\))?\}" % re.escape(shape), line))


def _fitting_path(text):
    """The instructions of a compiled module that run when every
    `conditional` takes its LAST branch (`lax.cond`'s true one: a batch
    within the capacities), from ENTRY down through fusions, calls and
    loops."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    entry = re.search(r"\nENTRY %([\w.-]+) ", text).group(1)
    seen, todo, lines = set(), [entry], []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            lines.append(line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                todo.append(branches.group(1).split(",")[-1].strip(" %"))
            todo += re.findall(
                r"(?:calls|body|condition|to_apply|true_computation)=%"
                r"([\w.-]+)", line)
    return lines


def _assert_compact_gather(text, slab, compact, table):
    """`gather_rows` as compiled (PR 34): the distinct branch of its cond
    reads the slots (a result of shape `slab`) out of the `compact` table
    and never out of the whole `table`, which the other branch reads; and
    the compiler keeps the compact table in fast memory, which is what
    the gather's 1.9 ns a row against 10.2 rests on (at the ranking's
    282,624 rows FM's stays in HBM and the step LOSES 2.3 ms)."""
    assert _slab_gathers(text, slab) == {
        "branch_1": compact, "branch_0": table}, _slab_gathers(text, slab)
    spaces = _compact_table_spaces(text, compact)
    assert spaces == {"S(1)"}, f"the compact table lives in {spaces}"


def fm_minibatch_step():
    """ONE un-scanned make_fm_step_minibatch step at the geometry of the
    benchmark's cell fm_criteo.stream (-dims 2^26 -factors 5, B=32768,
    L=39, float32, unit values elided). The compiled program must hold no
    loop the compiler made: the compiler wrote the packed unpack's
    [B, L, P, Wf] reshape as two 128-trip loops over lanes, 43% of the
    step's device time (PERF.md, PR 24 / PR 25), and on the CPU that
    tier-1 runs on such a reshape costs nothing. The one `while` it holds
    since PR 34 is `gather_rows`' own: the fill of the compact table, a
    block a trip up to the batch's count of distinct rows."""
    K, B, L = 5, 32768, 39
    Wf, Pk = fm.fm_pack_geometry(K)
    Np = (1 << 26) // Pk
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_fm_step_minibatch(get_loss("logloss"), opt, LAMS, K)
    table = _sds((Np, Pk * Wf), jnp.float32)
    compiled = step.lower(
        {"T": table, "w0": _sds((), jnp.float32)},
        {"T": {"gg": table}, "w0": {"gg": _sds((), jnp.float32)}},
        _sds((), jnp.float32), _sds((B, L), jnp.int32), None,
        _sds((B,), jnp.float32), _sds((B,), jnp.float32)
    ).compile()
    text = compiled.as_text()
    loops = re.findall(r" while\(.*?op_name=\"([^\"]*)\"", text)
    assert len(loops) == 1 and "/hm.gather/while" in loops[0], \
        f"while op(s) {loops}, expected the compact table's fill alone"
    # the distinct-row tail (PR 28): one cond between it and the dense
    # tail, the donated tables passing through both in place (the row
    # kernel aliases them: ONE Mosaic kernel since PR 30), and no more
    # temporaries than the dense tail's G beside the gradient slab (2.80 GB
    # read here in PRs 30 and 34: the gather's compact table, 101 MB, and
    # the compact gradient live where the dense branch's G would). In
    # front (PR 34): a cond that lists the distinct rows only for a batch
    # within the capacity, and the gather's own, the slab leaving either
    # of its branches without a copy
    cap = fm.tail_cap(B * L, Np)
    assert cap, "the cell's shape must offer the tail"
    gcap, W = fm.gather_cap(cap, Pk * Wf, 4), Pk * Wf
    conds = text.count(" conditional(")
    assert conds == 3, \
        f"{conds} conditional op(s), expected the list, gather and tail"
    _assert_compact_gather(text, f"f32[{L},{B},{W}]", f"f32[{gcap},{W}]",
                           f"f32[{Np},{W}]")
    slab_copies = re.findall(r"= f32\[(?:%d,%d|%d),%d\]\S* copy\("
                             % (L, B, L * B, Pk * Wf), text)
    assert not slab_copies, f"{len(slab_copies)} copies of the slab"
    kernels = text.count("tpu_custom_call")
    assert kernels == 1, f"{kernels} Mosaic kernels, expected update_rows"
    copies = re.findall(r"= f32\[%d,%d\]\S* copy\(" % (Np, Pk * Wf), text)
    assert not copies, f"{len(copies)} copies of a whole table"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2.85e9, f"{temp / 1e9:.2f} GB of temporaries"


def ffm_joint_megastep():
    """The megastep (two steps: a scan's body compiles once) of
    make_ffm_step_fused at the geometry of the benchmark's cell
    ffm_criteo_joint.stream (-dims 2^28 -fields 39 -factors 4 -halffloat:
    a [4194304, 164] bfloat16 table and its float32 AdaGrad state,
    B=32768, L=39, fieldmajor, unit values elided), its tail through
    rows_update (PR 32). The megastep and not the one step: the chip keeps a
    164-lane table transposed (`{0,1}`), every program relayouts it on the
    way in and out, and the cell pays that once a dispatch.

    Mosaic's verdict on this shape was learned here: it compiles no row
    copy of a 164-lane array and no bfloat16 pair (ops/rows_pallas.py has
    its words), so the distinct rows go through XLA's gather and scatter
    in blocks: one `conditional` between the tails and, since PR 34, one
    around the list of distinct rows and one between the gathers (the
    distinct branch reads the slots out of the compact [cap, 164] table,
    kept in fast memory, the other out of the whole one), three
    `while` (the scan, the blocks' loop and the compact table's fill,
    whose trips follow the batch's count), no Mosaic kernel, the four
    relayouts of the tables at the megastep's two ends (the dense tail's
    program has the same four) and none inside the scan, and no more
    temporaries than the dense tail's program: 15.04 GB read against its
    15.05 (both hold the dense branch's G; the figure counts buffers the
    chip overlays, the cell peaks at 4.95 GB)."""
    from hivemall_tpu.ops.scan import make_megastep
    Fj, B, L, ks = 39, 32768, 39, 2
    Mr, W = 1 << 22, Fj * K + 8
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_ffm_step_fused(get_loss("logloss"), opt, LAMS, Fj, K,
                                  fieldmajor=True, unit_val=True)
    T, gg = _sds((Mr, W), jnp.bfloat16), _sds((Mr, W), jnp.float32)
    cap = fm.tail_cap(B * L, Mr, W, 2)
    assert cap, "the cell's shape must offer the tail"
    assert not rows_pallas.kernel_takes((T, gg))
    compiled = make_megastep(step.core).lower(
        {"T": T, "w0": _sds((), jnp.float32)},
        {"T": {"gg": gg}, "w0": {"gg": _sds((), jnp.float32)}},
        _sds((), jnp.float32), _sds((ks,), jnp.int32),
        _sds((ks, B, L), jnp.int32), None, _sds((ks, B), jnp.float32), None,
        None).compile()
    text = compiled.as_text()
    conds, loops = text.count(" conditional("), text.count(" while(")
    assert conds == 3, \
        f"{conds} conditional op(s), expected the list, gather and tail"
    assert loops == 3, \
        f"{loops} while op(s), expected the scan, the blocks and the fill"
    _assert_compact_gather(
        text, f"bf16[{B},{L},{W}]",
        f"bf16[{fm.gather_cap(cap, W, 2)},{W}]", f"bf16[{Mr},{W}]")
    kernels = text.count("tpu_custom_call")
    assert kernels == 0, f"{kernels} Mosaic kernels in the XLA-rows tail"
    copy = r"= (?:bf16|f32)\[%d,%d\]\S* copy\(" % (Mr, W)
    everywhere = len(re.findall(copy, text))
    at_the_ends = len(re.findall(copy, text[text.index("\nENTRY "):]))
    assert everywhere == at_the_ends == 4, \
        f"{everywhere} copies of a whole table, {at_the_ends} in ENTRY"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 15.1e9, f"{temp / 1e9:.2f} GB of temporaries"


def ffm_joint_megastep_tp4():
    """The same megastep as a chip of four runs it (PR 36): the cell
    ffm_criteo_joint_tp4.stream_mesh's geometry (-dims 2^30: 16,777,216
    rows over `-mesh dp=1,tp=4`, so [4194304, 164] a chip, the one-chip
    flagship's block), `make_ffm_step_fused(mesh=...)` under `shard_map`
    over tp (two of them, the slabs' sum between). A chip's program is
    the one chip's: no zero fill of a float32 [4194304, 164] gradient and no table-sized AdaGrad pass
    outside the dense branch of the tail's `conditional`, the compact
    table of the chip's own distinct rows in fast memory, ONE all-reduce,
    of the slab (bf16[32768,39,164], 419 MB) and of nothing else but
    scalars (the step's stats), a chip's outputs its block of the state
    and its temporaries the one-chip program's."""
    from hivemall_tpu.ops.scan import make_megastep
    Fj, B, L, ks, tp = 39, 32768, 39, 2, 4
    Mr, W = 1 << 24, Fj * K + 8
    mesh = _mesh_1x4()
    rows, everywhere = NamedSharding(mesh, P("tp", None)), \
        NamedSharding(mesh, P())
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_ffm_step_fused(get_loss("logloss"), opt, LAMS, Fj, K,
                                  fieldmajor=True, unit_val=True, mesh=mesh)
    cap = fm.tail_cap(B * L, Mr // tp, W, 2)
    assert cap == 174080, cap           # the one-chip flagship's
    w0 = _sds((), jnp.float32, everywhere)
    compiled = make_megastep(step.core).lower(
        {"T": _sds((Mr, W), jnp.bfloat16, rows), "w0": w0},
        {"T": {"gg": _sds((Mr, W), jnp.float32, rows)}, "w0": {"gg": w0}},
        w0, _sds((ks,), jnp.int32, everywhere),
        _sds((ks, B, L), jnp.int32, everywhere), None,
        _sds((ks, B), jnp.float32, everywhere), None, None).compile()
    text = compiled.as_text()
    conds, loops = text.count(" conditional("), text.count(" while(")
    assert conds == 3, \
        f"{conds} conditional op(s), expected the list, gather and tail"
    assert loops == 3, \
        f"{loops} while op(s), expected the scan, the blocks and the fill"
    block = f"[{Mr // tp},{W}]"
    _assert_compact_gather(
        text, f"bf16[{B},{L},{W}]",
        f"bf16[{fm.gather_cap(cap, W, 2)},{W}]", f"bf16{block}")
    assert "tpu_custom_call" not in text
    # what the parent's GSPMD program ran every step, the zero fill of a
    # table-sized G and the elementwise pass over table and state, is in
    # the dense branch of the tail's cond and nowhere a fitting batch goes
    # (the relayouts at the megastep's two ends are copies, the blocks'
    # row scatters update in place)
    dense = [line.split(" = ")[0].strip() for line in _fitting_path(text)
             if re.search(r"= \(?[^=]*%s[^=]* (broadcast\(|fusion\(.*"
                          r"kind=kLoop)" % re.escape(block), line)]
    assert not dense, f"table-sized passes a fitting batch runs: {dense}"
    # named by the partitioner as its own are (`mesh.collective_share`
    # reads a trace's ops by name; a `lax.psum` would be `psum.N`)
    reduces = re.findall(r"%all-reduce\S* = (\w+\[[\d,]*\])\S* "
                         r"all-reduce(?:-start)?\(", text)
    assert len(reduces) == len(re.findall(r" all-reduce(?:-start)?\(",
                                          text)), "an all-reduce unnamed"
    big = [r for r in reduces if r != f"bf16[{B},{L},{W}]"
           and np.prod([int(d) for d in re.findall(r"\d+", r)[1:]]) > 64]
    assert reduces.count(f"bf16[{B},{L},{W}]") == 1 and not big, reduces
    for kind in ("all-gather", "collective-permute", "all-to-all",
                 "reduce-scatter"):
        assert f" {kind}(" not in text and f" {kind}-start(" not in text, \
            f"{kind} in the sharded megastep"
    # a chip's outputs are its block of the state, its temporaries no
    # more than the one chip's program reads (15.04 GB, the dense
    # branch's buffers, which the chip overlays: no bound, see there)
    mem = compiled.memory_analysis()
    state = Mr // tp * W * (2 + 4)
    assert abs(mem.output_size_in_bytes - state) < 0.05 * state, mem
    assert mem.temp_size_in_bytes <= 15.1e9, mem


def state_init():
    """The fused tables' initialiser (models/fm.py `_fused_state_init`,
    the function `LearnerBase._make_state` jits) at the size no one chip
    holds: `train_ffm -dims 2^30 -halffloat` over tp=4, each output in its
    row sharding. A chip holds its quarter of the table and of the AdaGrad
    state (4.13 GB; 4.23 as the compiler lays it out) and draws it
    locally, with no collective in the program and the whole of it inside
    the chip."""
    from hivemall_tpu.models.fm import _fused_state_init
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    mesh = _mesh_1x4()
    rows, everywhere = NamedSharding(mesh, P("tp", None)), \
        NamedSharding(mesh, P())
    Mr, FK = 1 << 24, 39 * 4
    init = _fused_state_init(opt, Mr, 1, FK, FK + 8, jnp.bfloat16)
    out = ({"w0": everywhere, "T": rows},
           {"w0": {"gg": everywhere}, "T": {"gg": rows}})
    compiled = jax.jit(init, out_shardings=out).lower(
        _sds(key.shape, key.dtype, everywhere),
        _sds((), jnp.float32, everywhere)).compile()
    text = compiled.as_text()
    for kind in ("all-reduce", "all-gather", "collective-permute",
                 "all-to-all", "reduce-scatter"):
        assert f" {kind}(" not in text, f"{kind} in the sharded initialiser"
    mem = compiled.memory_analysis()
    quarter = Mr // 4 * (FK + 8) * (2 + 4)
    assert abs(mem.output_size_in_bytes - quarter) < 0.05 * quarter, \
        f"{mem.output_size_in_bytes / 1e9:.2f} GB of outputs a chip"
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


def linear_megastep():
    """train_classifier -loss logloss -opt adagrad (AdaGrad-RDA) at the
    geometry of the benchmark's cell logreg_criteo.stream (-dims 2^28:
    `w`, `u`, `gg` three float32 [2^28] arrays; B=32768, L=39, unit values
    elided): the state's jitted initialiser (models/linear.py
    `_linear_state_init`, what `LearnerBase._make_state` jits) and the
    megastep (two steps: a scan's body compiles once). The state is 3.22
    GB and stays inside one chip with the step's temporaries, each of
    the four phase scopes names ops of the step, and no Mosaic kernel is
    in it."""
    from hivemall_tpu.models.linear import _linear_state_init
    from hivemall_tpu.ops.linear import make_linear_step
    from hivemall_tpu.ops.scan import make_megastep
    dims, B, L, ks = 1 << 28, 32768, 39, 2
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         power_t=0.1, reg="rda", lam=1e-6)
    assert opt.name == "adagrad_rda"
    init = jax.jit(_linear_state_init(opt, dims, jnp.float32),
                   out_shardings=(DEV0, {"u": DEV0, "gg": DEV0})).lower() \
        .compile().memory_analysis()
    assert 0 <= init.output_size_in_bytes - 3 * 4 * dims < 4096 \
        and init.temp_size_in_bytes == 0, init
    table = _sds((dims,), jnp.float32)
    compiled = make_megastep(
        make_linear_step(get_loss("logloss"), opt).core, none_val=True
    ).lower(table, {"u": table, "gg": table}, _sds((), jnp.float32),
            _sds((ks,), jnp.int32), _sds((ks, B, L), jnp.int32), None,
            _sds((ks, B), jnp.float32), None, None).compile()
    text = compiled.as_text()
    for scope in ("hm.gather", "hm.grad", "hm.scatter", "hm.update"):
        assert f"/{scope}/" in text, f"no op under {scope}"
    assert "tpu_custom_call" not in text
    mem = compiled.memory_analysis()
    # donated: the three arrays are updated in place. The dense gradient
    # needs no array of its own: RDA rebuilds `w` from `u` and `gg`, so
    # after the margin's gather the compiler scatters into `w`'s buffer
    # (5.9 MB of temporaries read here, not 1.07 GB)
    assert mem.alias_size_in_bytes >= 3 * 4 * dims
    assert mem.temp_size_in_bytes < 4 * dims, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30


N, D, BINS = 1 << 20, 28, 64            # HIGGS-shaped trees, cut to 1M rows


def _hist(fn, n_nodes, fast):
    jax.jit(lambda b, l, w: fn(b, l, w, n_nodes, BINS, fast=fast)).lower(
        _sds((N, D), jnp.uint8), _sds((N,), jnp.int32),
        _sds((N, 3), jnp.float32)).compile()


def hist_flat():
    for m in (1, 8):
        _hist(pallas_hist.level_histogram, m, False)


def hist_dense():
    dp = -(-D // 8) * 8
    for m, fast in ((1, True), (64, True), (8, False)):
        jax.jit(lambda b, l, w: pallas_hist.level_histogram_dense(
            b, l, w, m, BINS, fast=fast)).lower(
            _sds((dp, N), jnp.uint8), _sds((N,), jnp.int32),
            _sds((N, 3), jnp.float32)).compile()


def hist_sorted():
    for m in (64, 256):
        _hist(pallas_hist.level_histogram_sorted, m, False)


CASES = {f.__name__: f for f in (parts_step, parts_accum_kernel_2x2,
                                 parts_step_sharded, fm_minibatch_step,
                                 ffm_joint_megastep, ffm_joint_megastep_tp4,
                                 hist_flat, hist_dense,
                                 hist_sorted, state_init, linear_megastep)}

if __name__ == "__main__":
    for name in sys.argv[1:]:
        t0 = time.perf_counter()
        CASES[name]()
        print(json.dumps({"case": name, "ok": True, "seconds":
                          round(time.perf_counter() - t0, 1)}), flush=True)
