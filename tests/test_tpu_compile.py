"""Kernels of the main path compiled at their real shapes for a DESCRIBED
TPU v5e (``jax.experimental.topologies``: the chip's compiler is
installed here, the chip is not). Nothing runs: these tests say what the
compiler makes of a program, never how fast it is. They skip where no
TPU compiler can describe the topology. Keep every such test in THIS
file: the process that describes the topology holds the TPU library."""

import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _kernel_calls(text):
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _only_the_fold_yields_a_shard(text, call, shape):
    """The one array of ``shape`` (a float per row of the shard) that the
    round makes outside a fused computation is the fold kernel's ``d2``,
    seen through a bitcast of the tuple element of ``call``: the draw's
    keys are never held. Parameters and tuple elements only name an array."""
    made, fused = [], False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", ln)
        if head:
            fused = "fused_computation" in head.group(1)
        got = re.search(rf"= {re.escape(shape)}\S* ([\w-]+)\(%?([\w.-]*)", ln)
        if got and not fused and got.group(1) not in ("parameter",
                                                      "get-tuple-element"):
            made.append(got.groups())
    (op, src), = made
    kernel = re.match(r"\s*%(\S+) = ", call).group(1)
    assert op == "bitcast"
    assert re.search(rf"%{re.escape(src)} = \S+ get-tuple-element\("
                     rf"%{re.escape(kernel)}\)", text)


@pytest.mark.parametrize("l", [20, 1], ids=["later_round", "first_round"])
def test_kmpp_round_compiles_to_one_streamed_kernel(monkeypatch, one_chip, l):
    """A k-means|| round of ``kmeans-fit`` (1,526 blocks of 20 × 512 ×
    128 float32; 20 new candidates, or the first round's one): the fold
    is ONE ``tpu_custom_call``, the state aliased through it; no array of
    a block's distances ``(l, S, 128)`` or of a block's copy ``(d, S,
    128)`` exists; the table reaches the kernel as the program's own
    parameter, uncopied. The draw keys the whole shard for its block
    maxima and keeps none of it: the round's temporaries stay under
    600 MB (645,120 bytes compiled here for PR 36; the per-block draw it
    replaced read 612,864) and the one array of a shard's rows made
    outside a fusion is the fold kernel's own ``d2``."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    # the rig's backend is the CPU: compile the kernel, not its interpreter
    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    nbl, d, S, cap = 1526, 20, 512, 101

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def round_(Xs, Ws, d2, nearest, new, off, key, last):
        d2, nearest = K._kmpp_fold(Xs, Ws, d2, nearest, new, off, "kernel")
        return d2, nearest, K._kmpp_draw(
            Ws, d2, nearest, jax.random.wrap_key_data(key), 0, last, cap, 20)

    with jax.enable_x64(False):                      # as on the chip
        compiled = jax.jit(round_, donate_argnums=(2, 3)).lower(
            sd((nbl, d, S, 128), jnp.float32), sd((nbl, S, 128), jnp.float32),
            sd((nbl, S, 128), jnp.float32), sd((nbl, S, 128), jnp.int32),
            sd((l, d), jnp.float32), sd((), jnp.int32), sd((2,), jnp.uint32),
            sd((), jnp.bool_)).compile()
    text = compiled.as_text()

    calls = _kernel_calls(text)
    assert len(calls) == 1
    assert "kmpp_fold" in calls[0]
    assert "output_to_operand_aliasing" in calls[0]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 600e6, temp
    _only_the_fold_yields_a_shard(text, calls[0], f"f32[{nbl},{S},128]")
    for shape in {f"[{l},{S},128]", f"[{d},{S},128]"} - {f"[1,{S},128]"}:
        assert shape not in text, shape
    # the table: a parameter of the entry computation that reaches the
    # kernel through a bitcast (its rows seen as register tiles, the same
    # bytes in place); never copied or laid out anew
    table = f"f32[{nbl},{d},{S},128]"
    made = [ln.strip() for ln in text.splitlines()
            if re.search(rf"= {re.escape(table)}\S* (?!parameter)", ln)]
    assert made == []
    param = re.search(rf"(\S+) = {re.escape(table)}\S* parameter\(0\)", text)
    tiled = re.escape(f"f32[{nbl},{d},{S // 8},8,128]")
    made = re.findall(rf"(\S+) = {tiled}\S* (\w+)\((\S+?)\)", text)
    assert made and all(op == "bitcast" and src == param.group(1)
                        for _, op, src in made)
    assert any(name in calls[0] for name, _, _ in made)


def test_kmpp_round_compiles_a_worker_on_four_chips(monkeypatch, topo):
    """The same round inside a ``shard_map`` as the engine makes it
    (``check_vma=False``), the table's blocks split over a 2 x 2 v5e: one
    kernel a worker over its own 382 blocks, the state aliased, and a
    quarter of the table a chip; a worker's temporaries stay under 200 MB
    (613,376 bytes compiled here for PR 36; 483,840 before) and its keys
    are never held at the shard's size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from alink_tpu.common.compat import shard_map
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("d",))
    nbl, d, S, l, cap = 382, 20, 512, 20, 101

    def sd(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def round_(Xs, Ws, d2, nearest, new, off, key, last):
        d2, nearest = K._kmpp_fold(Xs, Ws, d2, nearest, new, off, "kernel")
        (kv, _, _), _, _, counts = K._kmpp_draw(
            Ws, d2, nearest, jax.random.wrap_key_data(key),
            jax.lax.axis_index("d") * nbl, last, cap, l)
        return d2, nearest, jax.lax.all_gather(kv, "d"), \
            jax.lax.psum(counts, "d")

    rows = (4 * nbl, S, 128)
    with jax.enable_x64(False):
        compiled = jax.jit(shard_map(
            round_, mesh=mesh, in_specs=(P("d"),) * 4 + (P(),) * 4,
            out_specs=(P("d"), P("d"), P(), P()), check_vma=False),
            donate_argnums=(2, 3)).lower(
                sd((4 * nbl, d, S, 128), jnp.float32, P("d")),
                sd(rows, jnp.float32, P("d")), sd(rows, jnp.float32, P("d")),
                sd(rows, jnp.int32, P("d")), sd((l, d), jnp.float32),
                sd((), jnp.int32), sd((2,), jnp.uint32),
                sd((), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 1 and f"f32[{nbl},{d},{S // 8},8,128]" in calls[0]
    assert "output_to_operand_aliasing" in calls[0]
    table = 4 * nbl * d * S * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 0.3 * table
    assert mem.temp_size_in_bytes < 200e6, mem.temp_size_in_bytes
    _only_the_fold_yields_a_shard(text, calls[0], f"f32[{nbl},{S},128]")


def test_lloyd_superstep_compiles_to_one_streamed_kernel(monkeypatch,
                                                         one_chip):
    """A Lloyd superstep of ``kmeans-fit`` (1,526 blocks of 20 × 512 ×
    128 float32, k = 10): the pass is ONE ``tpu_custom_call``; no array of
    a block's distances or masked weights ``(k, S, 128)`` or of a block's
    copy ``(d, S, 128)`` exists; the table reaches the kernel as the
    program's own parameter, uncopied."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    # the rig's backend is the CPU: take the kernel, and compile it
    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    monkeypatch.setattr(K, "lloyd_path", lambda *a: "kernel")
    nbl, d, S, k = 1526, 20, 512, 10

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(Xs, Ws, C):
        return K._lloyd_update(K._lloyd_pass(Xs, Ws, C, "EUCLIDEAN"), C)

    with jax.enable_x64(False):                      # as on the chip
        text = jax.jit(step).lower(
            sd((nbl, d, S, 128), jnp.float32), sd((nbl, S, 128), jnp.float32),
            sd((k, d), jnp.float32)).compile().as_text()

    calls = _kernel_calls(text)
    assert len(calls) == 1
    assert "lloyd_pass" in calls[0]
    for shape in (f"[{k},{S},128]", f"[{d},{S},128]"):
        assert shape not in text, shape
    table = f"f32[{nbl},{d},{S},128]"
    made = [ln.strip() for ln in text.splitlines()
            if re.search(rf"= {re.escape(table)}\S* (?!parameter)", ln)]
    assert made == []
    param = re.search(rf"(\S+) = {re.escape(table)}\S* parameter\(0\)", text)
    tiled = re.escape(f"f32[{nbl},{d},{S // 8},8,128]")
    made = re.findall(rf"(\S+) = {tiled}\S* (\w+)\((\S+?)\)", text)
    assert made and all(op == "bitcast" and src == param.group(1)
                        for _, op, src in made)
    assert any(name in calls[0] for name, _, _ in made)


def test_lloyd_superstep_compiles_a_worker_on_four_chips(monkeypatch, topo):
    """The same superstep inside a ``shard_map`` as the engine makes it
    (``check_vma=False``), the table's blocks split over a 2 x 2 v5e: one
    kernel a worker over its own 382 blocks, the buffer summed by one
    all-reduce, and a quarter of the table a chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from alink_tpu.common.compat import shard_map
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    monkeypatch.setattr(K, "lloyd_path", lambda *a: "kernel")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("d",))
    nbl, d, S, k = 382, 20, 512, 10

    def sd(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def step(Xs, Ws, C):
        buf = jax.lax.psum(K._lloyd_pass(Xs, Ws, C, "EUCLIDEAN"), "d")
        return K._lloyd_update(buf, C)

    with jax.enable_x64(False):
        compiled = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P("d"), P("d"), P()),
            out_specs=P(), check_vma=False)).lower(
                sd((4 * nbl, d, S, 128), jnp.float32, P("d")),
                sd((4 * nbl, S, 128), jnp.float32, P("d")),
                sd((k, d), jnp.float32)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 1 and f"f32[{nbl},{d},{S // 8},8,128]" in calls[0]
    assert len(re.findall(r"= \S+ all-reduce(?:-start)?\(", text)) == 1
    table = 4 * nbl * d * S * 128 * 4
    assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * table


def test_als_half_sweep_compiles_with_the_solve_kernel_and_small_temps(
        monkeypatch, one_chip):
    """A user half-sweep of ``als-fit`` (252.8 million grouped ratings as
    1,975,296 rows of 128, 624,961 item factor rows of 128 lanes, rank
    100): the batches' solves are ``tpu_custom_call``s named ``als_solve``
    (one a tier), and the program's temporaries stay a few batches wide. A
    16-wide view of a grouped column, which the chip pads eightfold, once
    asked for 10.2 GB here (PERF.md §6, PR 33)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import smallsolve as kernel
    from alink_tpu.operator.common.recommendation import als as A

    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    monkeypatch.setattr(kernel, "pallas_available", lambda: True)
    U, I, f, rows = 1_000_990, 624_961, 100, 3858 * 512
    p = A.AlsTrainParams(rank=f, num_iter=1, lambda_reg=1.4)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def half_sweep(other, ids, val, off, cnt, order, rank_of, old):
        return A._half_sweep(other, ids, val, off, cnt, order, rank_of, U, p,
                             1, 0, old=old)

    with jax.enable_x64(False):                      # as on the chip
        compiled = jax.jit(half_sweep).lower(
            sd((I, 128), jnp.float32), sd((rows, 128), jnp.int32),
            sd((rows, 128), jnp.float32), sd((U + 2,), jnp.int32),
            sd((U,), jnp.int32), sd((U,), jnp.int32), sd((U,), jnp.int32),
            sd((U, 128), jnp.float32)).compile()
    calls = _kernel_calls(compiled.as_text())
    assert len(calls) == len(A._tiers(U, 1)) == 2
    assert all("als_solve" in c for c in calls)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
    # the grouped columns reach the loop as the parameters they are
    assert mem.argument_size_in_bytes < 2 * rows * 128 * 4 + 1.0e9


#: ``temp_size_in_bytes`` of the PARENT's ``jit_gbdt_grow`` (PR 33's tree,
#: every node's histogram built), compiled the same way: one chip, and a
#: worker of the 2 x 2. Mostly the margins' copy (460 MB on one chip).
_GROW_TEMP_BEFORE_PR34 = {1: 474_684_928, 4: 808_208_896}


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "worker_of_2x2"])
def test_gbdt_grow_compiles_with_the_halved_histogram_product(topo, chips):
    """``jit_gbdt_grow`` of ``gbdt-fit`` (1,755 blocks of 13 x 512 x 128
    uint8 bins, depth 6, 128 bins), the whole engine program lowered from
    shapes: the widest one-hot product has 16 built nodes x 6 = 96
    right-hand columns where every node built asked 192; the longest
    all-reduce is the 16 built nodes' Kahan pair; and the program asks
    for the parent's temporaries to within 1 % (it keeps a level's joined
    histogram for the next level's subtraction, at most 320 KB, and
    nothing a row: 474.68 -> 475.35 MB on one chip, 808.2 -> 811.8 MB a
    worker, compiled here for PR 34)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.tree import trainers as T

    env = MLEnvironment(parallelism=chips,
                        devices=list(topo.devices[:chips]))
    blocks, F, S, bins, depth = -(-1755 // chips), 13, 512, 128, 6
    p = T.TreeTrainParams(num_trees=4, max_depth=depth, n_bins=bins,
                          min_samples_leaf=100)
    rows = jax.ShapeDtypeStruct((chips * blocks, S, 128), jnp.float32)
    with jax.enable_x64(False):                      # as on the chip
        compiled = T._grow_queue(
            env, jax.ShapeDtypeStruct((chips, blocks, F, S, 128), jnp.uint8),
            rows, rows, 0.0, 7, p, False, F, S * 128, "onehot",
            None).lowered().compile()
    text = compiled.as_text()
    assert "jit_gbdt_grow" in text
    widths = [int(np.prod([int(d) for d in dims.split(",")]))
              for dims in re.findall(
                  rf"= f32\[{F},{bins},([0-9,]+)\]\S* convolution\(", text)]
    assert widths and max(widths) == (1 << depth - 2) * 6 == 96, widths
    if chips > 1:
        longest = max(int(n) for n in re.findall(
            r"= f32\[(\d+)\]\S* all-reduce(?:-start)?\(", text))
        assert longest == 2 * 16 * F * bins * 3 + 2 * 16
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.01 * _GROW_TEMP_BEFORE_PR34[chips], temp


def test_linear_step_program_compiles_beside_the_byte_table(topo):
    """``jit_linear_qn`` and ``jit_linear_moments`` of ``softmax-fit`` (124
    blocks of 784 x 512 x 128 uint8, 9 coefficient rows, history 10, 20
    supersteps), the whole engine programs lowered from shapes: the step
    program's products are MXU products of the stacked bfloat16 parts (27
    = 3 x 9 rows), the table is an argument and never copied (arguments
    6.44 GB: the table, labels, weights), and everything a superstep makes
    beside it stays under 1.5 GB (the kept logits, 292 MB, are the loop's
    carry; compiled here for PR 37: 7 MB of temporaries)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.linear import base as B
    from alink_tpu.operator.common.optim import optimizers as O
    from alink_tpu.operator.common.optim.objfunc import SoftmaxObjFunc

    env = MLEnvironment(parallelism=1, devices=list(topo.devices[:1]))
    nb, d, S, k = 124, 784, 512, 10
    table = nb * d * S * 128
    with jax.enable_x64(False):                      # as on the chip
        parts = {"X": jax.ShapeDtypeStruct((nb, d, S, 128), jnp.uint8),
                 "y": jax.ShapeDtypeStruct((nb, S, 128), jnp.int32),
                 "w": jax.ShapeDtypeStruct((nb, S, 128), jnp.float32)}
        consts = {"scale": np.ones(d, np.float32),
                  "shift": np.zeros(d, np.float32)}
        obj = SoftmaxObjFunc(k, d + 1, reg_free_cols=1)
        hyp = {"hyp_l1": 0.0, "hyp_l2": 1e-6, "hyp_lr": 1.0, "hyp_eps": 1e-6}
        step = O.qn_queue(obj, parts, consts, hyp,
                          np.zeros(obj.dim, np.float32), 20, 0, env, False,
                          10).lowered().compile()
        moments = B.moments_queue(env, parts["X"],
                                  parts["w"]).lowered().compile()
    text = step.as_text()
    assert "jit_linear_qn" in text and "jit_linear_moments" in moments.as_text()
    assert re.search(r"= f32\[27,\d+\]\S* convolution\(", text)      # forward
    assert re.search(r"= f32\[27,784\]\S* convolution\(", text)     # backward
    mem = step.memory_analysis()
    assert table < mem.argument_size_in_bytes < table + 0.1e9
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1.5e9, (
        mem.temp_size_in_bytes, mem.output_size_in_bytes)
    assert moments.memory_analysis().temp_size_in_bytes < 0.5e9


def _mlp_step(monkeypatch, topo, h1):
    """``jit_mlp_qn`` of ``mlp-fit`` (124 blocks of 784 x 512 x 128 uint8,
    layers 784-``h1``-150-10, history 10, 5 supersteps) lowered from
    shapes and compiled for one chip, where Pallas can run (the rig's
    backend is the CPU, so the test says so in the kernel modules'
    place): ``(walk, rungs, text, memory analysis)``."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.kernels import mlp as kernel
    from alink_tpu.kernels import mlp_rungs
    from alink_tpu.operator.common.ann.mlp import (MlpObjFunc, rung_path,
                                                   walk_path)
    from alink_tpu.operator.common.optim import optimizers as O

    for module in (kernel, mlp_rungs):
        monkeypatch.setattr(module, "pallas_available", lambda: True)
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    env = MLEnvironment(parallelism=1, devices=list(topo.devices[:1]))
    nb, d, S = 124, 784, 512
    layers = [d, h1, 150, 10]
    with jax.enable_x64(False):                      # as on the chip
        parts = {"X": jax.ShapeDtypeStruct((nb, d, S, 128), jnp.uint8),
                 "y": jax.ShapeDtypeStruct((nb, S, 128), jnp.int32),
                 "w": jax.ShapeDtypeStruct((nb, S, 128), jnp.float32)}
        consts = {"scale": np.ones(d, np.float32),
                  "shift": np.zeros(d, np.float32)}
        obj = MlpObjFunc(layers).structure()
        hyp = {"hyp_l1": 0.0, "hyp_l2": 1e-4, "hyp_lr": 1.0, "hyp_eps": 1e-6}
        step = O.qn_queue(obj, parts, consts, hyp,
                          np.zeros(obj.dim, np.float32), 5, 0, env, False,
                          10).lowered().compile()
    text = step.as_text()
    assert "jit_mlp_qn" in text
    table = nb * d * S * 128
    mem = step.memory_analysis()
    assert table < mem.argument_size_in_bytes < table + 0.1e9
    return (walk_path(parts["X"], layers),
            rung_path(parts["X"], layers, np.float32), text, mem)


def _mlp_kernel_names(text):
    return sorted(re.search(r"%(mlp_\w+?)\.\d+ = ", c).group(1)
                  for c in _kernel_calls(text))


#: the temporaries of ``jit_mlp_qn`` at the cell's shapes with the ladder's
#: rungs as XLA's ``lax.map`` of a rung, compiled the same way
_MLP_TEMP_XLA_RUNGS = 492_090_880


def test_mlp_step_program_compiles_beside_the_byte_table(monkeypatch, topo):
    """``jit_mlp_qn`` of ``mlp-fit`` on the kernel walk: layer 1's products
    are the ``mlp_forward`` / ``mlp_backward`` Mosaic calls (gradient pass
    forward and backward, line pass forward, each in the first superstep
    and the loop's body), fed the table as the program's own argument; no
    product of the three stacked parts is left to XLA (the XLA walk's
    ``f32[1500,65536]`` / ``f32[3000,65536]`` / ``f32[1500,784]``), no
    block is copied out of the table, and the line pass's ``[z1; zd1]``
    come out of the kernel as two arrays that nothing slices. The ladder's
    rungs are the ``mlp_rungs`` call, fed those two arrays: XLA writes no
    rung's activations (of the fusions that make a ``(500, 65,536)``
    float32 array, the gradient pass's four are left, where the XLA rungs
    added one a rung loop) and takes no rung's products (the ``(10,
    65,536)`` logits are the gradient pass's two, where the XLA rungs
    added two). Everything a superstep makes beside the table stays under
    0.6 GB, and the temporaries fall below the XLA rungs' (compiled here:
    489.8 MB against 492.1, and 77 MB of outputs)."""
    walk, rungs, text, mem = _mlp_step(monkeypatch, topo, 500)
    assert (walk, rungs) == ("kernel", "kernel")
    assert _mlp_kernel_names(text) == ["mlp_backward"] * 2 \
        + ["mlp_forward"] * 4 + ["mlp_rungs"] * 2
    calls = _kernel_calls(text)
    assert all("u8[124,784,512,128]" in c for c in calls
               if "mlp_rungs" not in c)
    assert all(c.count("f32[500,65536]") >= 2 for c in calls
               if "mlp_rungs" in c)
    for shape in ("1500,65536", "3000,65536", "1500,784"):
        assert not re.search(rf"= f32\[{shape}\]\S* convolution\(", text)
    assert not re.search(r"= \w+\[784,512,128\]", text)        # a block
    assert not re.search(r"= f32\[500,65536\]\S* slice\(", text)
    assert len(re.findall(r"= f32\[500,65536\]\S* fusion\(", text)) == 4
    assert len(re.findall(r"= f32\[10,65536\]\S* convolution\(", text)) == 2
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 0.6e9, (
        mem.temp_size_in_bytes, mem.output_size_in_bytes)
    assert mem.temp_size_in_bytes < _MLP_TEMP_XLA_RUNGS, mem.temp_size_in_bytes


def test_mlp_step_program_compiles_over_the_kernels_budget(monkeypatch,
                                                           topo):
    """A layer 1 of 1,000 units: the line pass's stack of 2,000 rows is
    over the layer-1 kernels' VMEM budget, so ``walk_path`` reads ``xla``
    and the step program lowers the XLA walk, products of the three
    stacked parts against the block as before, no ``mlp_forward`` /
    ``mlp_backward`` call. The rungs' rule does not ask which walk layer
    1 took (both hand the rungs float ``z1`` and ``zd1``): they are the
    ``mlp_rungs`` call here too."""
    walk, rungs, text, _ = _mlp_step(monkeypatch, topo, 1000)
    assert (walk, rungs) == ("xla", "kernel")
    assert _mlp_kernel_names(text) == ["mlp_rungs"] * 2
    assert re.search(r"= f32\[6000,65536\]\S* convolution\(", text)
    assert re.search(r"= f32\[3000,784\]\S* convolution\(", text)


@pytest.mark.parametrize("sizes, rows", [
    ([500, 150, 10], 65536),
    ([500, 10], 65536),
    ([500, 2632, 10], 65536),
    ([3183, 150, 10], 65536),
    ([24, 12, 10], 384),
], ids=["the_cell", "one_hidden_layer", "the_widest_layer_2",
        "the_widest_layer_1", "tiles_of_one_lane_row"])
def test_mlp_rung_kernel_compiles_over_rung_paths_envelope(one_chip, sizes,
                                                           rows):
    """Mosaic takes the rung kernel at the corners of the envelope in
    which ``kernels/mlp_rungs.rung_path`` answers ``kernel`` (on a TPU a
    kernel it refused would raise where the XLA rungs ran before): the
    cell's 500-150-10 over a block of 65,536 rows, one hidden layer, the
    widest layer 2 the VMEM budget holds at the cell's layer 1 (2,632
    units; 2,640 reads ``xla``), the widest layer 1 at the cell's layer 2
    (3,183), and a block of 384 rows (tiles of 128). Every shape reads
    ``kernel`` from ``rung_path`` itself."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import mlp_rungs
    from alink_tpu.operator.common.ann.mlp import dot_highest

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mlp_rungs, "pallas_available", lambda: True)
        assert mlp_rungs.rung_path([784] + sizes, rows, "float32") == "kernel"

    def sd(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pairs = list(zip(sizes[:-1], sizes[1:]))
    uppers = tuple((sd((o, i)), sd((o, 1)), sd((o, i)), sd((o, 1)))
                   for i, o in pairs)
    with jax.enable_x64(False):
        compiled = jax.jit(lambda z1, zd1, y, w, up, s: mlp_rungs._losses(
            z1, zd1, y, w, up, s, dot=dot_highest, interpret=False)).lower(
                sd((sizes[0], rows)), sd((sizes[0], rows)),
                sd((1, rows), jnp.int32), sd((1, rows)), uppers,
                sd((11,))).compile()
    call, = _kernel_calls(compiled.as_text())
    assert "mlp_rungs" in call and f"f32[{sizes[0]},{rows}]" in call, call


@pytest.mark.parametrize("chips", [1, 4])
def test_linear_step_program_compiles_with_the_pass_kernels(monkeypatch, topo,
                                                            chips):
    """The same step program where Pallas can run (``pass_path`` reads
    ``kernel``; the rig's backend is the CPU, so the test says so in the
    kernel module's place): the two passes are ``qn_grad_pass`` and
    ``qn_line_pass``, one Mosaic call each in the first superstep and in
    the loop's body, fed the table as the program's own argument; no
    product of the stacked parts is left to XLA, no block is copied out
    of the table (nothing of a block's 51 MB is made), and the program
    asks for less beside its arguments than the XLA walk's 7 MB. On a
    2 x 2 v5e a worker walks its own 31 blocks and a superstep's two
    psums (7,069 and 13 floats; the first superstep's and the body's) are
    the program's only collectives."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.kernels import linear as kernel
    from alink_tpu.operator.common.optim import optimizers as O
    from alink_tpu.operator.common.optim.objfunc import SoftmaxObjFunc

    monkeypatch.setattr(kernel, "pallas_available", lambda: True)
    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    env = MLEnvironment(parallelism=chips, devices=list(topo.devices[:chips]))
    nb, d, S, k = 124, 784, 512, 10
    table = nb * d * S * 128
    with jax.enable_x64(False):
        parts = {"X": jax.ShapeDtypeStruct((nb, d, S, 128), jnp.uint8),
                 "y": jax.ShapeDtypeStruct((nb, S, 128), jnp.int32),
                 "w": jax.ShapeDtypeStruct((nb, S, 128), jnp.float32)}
        consts = {"scale": np.ones(d, np.float32),
                  "shift": np.zeros(d, np.float32)}
        obj = SoftmaxObjFunc(k, d + 1, reg_free_cols=1)
        hyp = {"hyp_l1": 0.0, "hyp_l2": 1e-6, "hyp_lr": 1.0, "hyp_eps": 1e-6}
        step = O.qn_queue(obj, parts, consts, hyp,
                          np.zeros(obj.dim, np.float32), 20, 0, env, False,
                          10).lowered().compile()
    text = step.as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 4 and all(
        f"u8[{nb // chips},{d},{S},128]" in c for c in calls), calls
    assert not re.search(r"= f32\[27,\d+\]\S* convolution\(", text)
    assert not re.search(rf"= \w+\[{d},{S},128\]", text)         # a block
    mem = step.memory_analysis()
    assert table / chips < mem.argument_size_in_bytes \
        < table / chips + 0.1e9
    assert mem.temp_size_in_bytes < 7.1e6, mem.temp_size_in_bytes
    if chips > 1:
        assert sorted(re.findall(r"= f32\[(\d+)\]\S* all-reduce(?:-start)?\(",
                                 text)) == ["13", "13", "7069", "7069"]
        assert len(re.findall(r"= \S+ all-reduce(?:-start)?\(", text)) == 4


@pytest.mark.parametrize("d, S, m, dtype", [
    (784, 512, 9, "int8"),
    (100, 512, 9, "uint8"),
    (129, 512, 9, "uint8"),
    (1000, 512, 9, "uint8"),
    (8192, 512, 9, "uint8"),
    (8192, 32, 40, "uint8"),
    (784, 512, 2, "uint8"),
    (784, 512, 40, "uint8"),
    (2, 32, 1, "uint8"),
], ids=["signed_bytes", "one_run_of_100", "a_last_run_of_49",
        "a_last_run_of_104", "the_widest", "the_widest_and_most_classes",
        "two_classes_more", "the_most_classes", "the_smallest"])
def test_pass_kernels_compile_over_pass_paths_envelope(one_chip, d, S, m,
                                                       dtype):
    """Mosaic takes both pass kernels at the corners of the envelope in
    which ``pass_path`` answers ``kernel`` (on a TPU a kernel it refused
    would raise where the XLA walk ran before): a run of features that is
    not whole bfloat16 registers (100), a last run stored at an offset
    that is (129: 80 + 49; 1,000: 7 x 128 + 104), the widest table (the
    block cut to 32 sublanes a step, 67 MB of table buffers under a
    107 MB limit), 1 to 40 coefficient rows, signed bytes, two features.
    Every shape reads ``kernel`` from ``pass_path`` itself."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import linear as kernel

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernel, "pallas_available", lambda: True)
        assert kernel.pass_path(dtype, d, S, m) == "kernel"
    nbl = 2

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    with jax.enable_x64(False):
        rows = sd((nbl, S, 128), jnp.float32)
        shard = (sd((nbl, d, S, 128), dtype), sd((nbl, S, 128), jnp.int32),
                 rows)
        coef = (sd((m, d), jnp.float32), sd((m,), jnp.float32))
        grad = jax.jit(lambda *a: kernel._grad_pass(*a, interpret=False)) \
            .lower(*shard, *coef).compile()
        line = jax.jit(lambda *a: kernel._line_pass(*a, interpret=False)) \
            .lower(*shard, sd((nbl, m, S, 128), jnp.float32), *coef,
                   sd((11,), jnp.float32)).compile()
    for compiled, name in ((grad, "qn_grad_pass"), (line, "qn_line_pass")):
        call, = _kernel_calls(compiled.as_text())
        assert name in call and f"[{nbl},{d},{S},128]" in call, call


@pytest.mark.parametrize("d, S, m, cuts, dtype", [
    (784, 512, 1000, 2, "uint8"),
    (784, 512, 1712, 2, "uint8"),
    (784, 512, 500, 1, "int8"),
    (129, 64, 20, 1, "uint8"),
    (1000, 512, 9, 1, "uint8"),
    (6455, 32, 1, 1, "uint8"),
    (2, 32, 1, 1, "uint8"),
], ids=["the_cells_line_pass", "the_most_rows", "signed_bytes",
        "a_last_run_of_17", "a_last_run_of_104", "the_widest", "the_smallest"])
def test_mlp_kernels_compile_over_walk_paths_envelope(one_chip, d, S, m, cuts,
                                                      dtype):
    """Mosaic takes both layer-1 kernels at the corners of the envelope in
    which ``kernels/mlp.walk_path`` answers ``kernel`` (on a TPU a kernel
    it refused would raise where the XLA walk ran before): the cell's
    line pass (1,000 rows cut in two outputs), the most rows the VMEM
    budget holds at the cell's width, signed bytes, a last run of features
    that is not whole registers, the widest table the budget holds, two
    features and one row. Every shape reads ``kernel`` from ``walk_path``
    itself."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import mlp as kernel

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernel, "pallas_available", lambda: True)
        assert kernel.walk_path(dtype, d, S, m) == "kernel"
    nbl = 2

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    with jax.enable_x64(False):
        table, i = sd((nbl, d, S, 128), dtype), sd((), jnp.int32)
        fwd = jax.jit(lambda X, i, A, b: kernel._forward(
            X, i, A, b, cuts, interpret=False)).lower(
                table, i, sd((m, d), jnp.float32),
                sd((m,), jnp.float32)).compile()
        bwd = jax.jit(lambda X, i, C: kernel._backward(
            X, i, C, interpret=False)).lower(
                table, i, sd((m // cuts, S * 128), jnp.float32)).compile()
    for compiled, name in ((fwd, "mlp_forward"), (bwd, "mlp_backward")):
        call, = _kernel_calls(compiled.as_text())
        assert name in call and f"[{nbl},{d},{S},128]" in call, call
