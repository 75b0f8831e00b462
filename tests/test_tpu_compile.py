"""Compile the main path at its real size for a described TPU v5e.

No chip is attached here; the TPU compiler is. It refuses what the chip
would refuse (a misaligned kernel slice, too much fast memory, a program
that does not fit 16 GB), so these cases guard every later PR at no chip
time. A compile that passes is not a chip run: nothing executes, and no
time or result comes out of this file (``chip_smoke.py`` is the run).

Rules this file keeps (``on-chip-measurement`` §2): the topology is
described inside a module-scoped fixture that skips when it cannot be —
never at import, never in ``conftest.py``, not ``autouse``; everything
built from it is built in a fixture or a test; the compiles run in this
process; and all such tests live in this ONE file, because only one
process of a test run may hold the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.ops import flash_attention as fa

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run would warn
    and recompile): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture()
def on_chip_kernels(monkeypatch, no_persistent_cache):
    """The kernel asks ``jax.default_backend()`` and would take its CPU
    (interpret) branch here; steer it in the test, not through an
    option of the program."""
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)


def _llama_shape():
    from dlrover_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig()
    # K/V are repeated to equal heads before the kernel (models/llama.py)
    return (8, cfg.max_seq_len, cfg.num_heads, cfg.head_dim)


FLASH_SHAPES = {
    "gpt2s_b32_t1024": (32, 1024, 12, 64),
    # one chip's share of the four-chip cell: 100 heads x 1024 under shard_map
    "gpt2xl_shard_b4_t1024": (4, 1024, 25, 64),
    "longseq_b4_t4096": (4, 4096, 12, 64),
    "llama_default": _llama_shape,
    "ragged_t100": (2, 100, 12, 64),
}


def fwd(q, k, v):  # (PARENT_PROGRAMS' texts carry the two functions' names)
    return fa.flash_attention(q, k, v, causal=True)


def fwd_bwd(q, k, v):
    return jax.grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )(q, k, v)


def _kernel_text(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape_id", list(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(
    shape_id, backward, one_chip, on_chip_kernels
):
    shape = FLASH_SHAPES[shape_id]
    shape = shape() if callable(shape) else shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(fwd_bwd if backward else fwd).lower(x, x, x).compile()
    text = _kernel_text(compiled)
    # forward is one kernel; backward adds the dk/dv and the dq passes,
    # whichever kernels the shapes chose
    assert text.count("tpu_custom_call") == (3 if backward else 1)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_two_head_sizes_compiles_for_v5e(
    backward, one_chip, on_chip_kernels
):
    """Latent attention's call at its published sizes: b4 x 4096, 32 heads,
    q and k 192 wide (128 nope + 64 rope), v and the output 128."""
    qk = jax.ShapeDtypeStruct((4, 4096, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(fwd_bwd if backward else fwd).lower(qk, qk, v).compile()
    assert _kernel_text(compiled).count("tpu_custom_call") == (3 if backward else 1)
    out = compiled.output_shardings  # shapes: out is v wide; dq, dk 192, dv 128
    assert len(jax.tree.leaves(out)) == (3 if backward else 1)


def test_grouped_expert_products_compile_for_v5e(
    one_chip, no_persistent_cache, monkeypatch
):
    """The held experts' three grouped products (megablox.gmm) with the row
    movements around them (a gather out, megablox.tgmm over token tiles
    back), forward and backward, at the benchmark's sizes: 16,384 tokens,
    a 32,768-row buffer, 16 experts of 2048 x 768."""
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    tokens, rows, d, f, held = 16384, 32768, 2048, 768, 16

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def experts(x, w_gate, w_up, w_down, token_of, sizes):
        moves = gm.row_order(token_of, jnp.sum(sizes), tokens)
        xs = gm.spread_rows(x, moves)
        h = jax.nn.silu(gm.grouped_matmul(xs, w_gate, sizes)) * (
            gm.grouped_matmul(xs, w_up, sizes))
        ys = gm.grouped_matmul(h, w_down, sizes)
        return gm.collect_rows(ys, moves)

    def loss_grads(*args):
        return jax.grad(
            lambda *a: experts(*a, *args[4:]).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3),
        )(*args[:4])

    compiled = jax.jit(loss_grads).lower(
        shape((tokens, d)), shape((held, d, f)), shape((held, d, f)),
        shape((held, f, d)), shape((rows,), jnp.int32), shape((held,), jnp.int32),
    ).compile()
    # two products forward (the third's value is dead under a summed loss),
    # for each of the three a product back and a weight gradient, and the
    # collecting kernel behind the spread's backward
    assert _kernel_text(compiled).count("tpu_custom_call") >= 9


@pytest.mark.parametrize("m,groups,row_tile,was", [
    (512, 128, 128, 512),  # sdar block pass: 16 rows x 4 positions x top-8
    (8192, 128, 128, 512),  # sdar prefill 1,024 wide x top-8
    (4096, 64, 128, 512),  # lfm2 prefill 1,024 wide x top-4
    (20480, 128, 128, 512),  # qwen3next prefill 2,048 wide x top-10, 128 of 512 experts held
    (64, 64, 64, 64),  # lfm2 decode: 16 rows x top-4
    (160, 128, 160, 160),  # qwen3next decode: 16 rows x top-10
    (65536, 16, 512, 512),  # mellum2 training buffer
    (32768, 16, 512, 512),  # joyai training buffer
])
def test_the_row_tile_follows_the_rows_a_group_can_hold(m, groups, row_tile, was):
    """``ops/grouped_matmul.py``'s rule at the cells' own shapes: the largest
    of 512, 256, 128 that divides ``m`` and is at most ``max(128, m //
    groups)``, ``m`` itself where none divides it; ``was`` is the tile by
    ``m`` alone, the rule until PR 60. The served prefills and the block pass
    leave 512 rows a tile; the two decode chunks (no tile divides their
    ``m``) and the two training buffers (2,048-4,096 rows a group) keep what
    they had, and with it their programs."""
    from dlrover_tpu.ops import grouped_matmul as gm

    for k, n in ((2048, 768), (768, 2048)):  # forward; the backward products call it again
        k_n_tiles = gm.megablox_tiling(m, k, n)[1:]
        assert gm.tiling_for(groups)(m, k, n) == (row_tile,) + k_n_tiles
        assert gm.megablox_tiling(m, k, n) == (was,) + k_n_tiles


def _gmm_row_tiles(text):
    """{(m, groups, row tile)} of the megablox ``gmm`` calls in a lowered
    text. The Mosaic body is serialized, but the call's operands say its
    grid: the group and tile ids it is handed are ``m // tm + groups - 1``
    long, the most (group, row tile) visits it can make."""
    import re

    calls = re.findall(
        r"@tpu_custom_call\(.*\(tensor<i32>, tensor<\d+xi32>, tensor<(\d+)xi32>, tensor<\d+xi32>, tensor<1xi32>, "
        r"tensor<(\d+)x\d+xbf16>, tensor<(\d+)x\d+x\d+xbf16>\) -> tensor<\d+x\d+xbf16>", text)
    return {(int(m), int(groups), int(m) // (int(visits) - int(groups) + 1)) for visits, m, groups in calls}


@pytest.mark.parametrize("tokens", [16, 1024], ids=["decode_step", "prefill"])
def test_routed_experts_in_a_serving_program_compile_for_v5e(
    one_chip, no_persistent_cache, monkeypatch, tokens
):
    """``MoeLayer`` as the server runs it at the published LFM2-24B-A2B
    widths: 64 experts of 2048 x 1536, top-4, over one token a slot (16
    slots: 64 rows, the whole buffer, one pass) and over a 1024-token
    prefill. The three grouped products are megablox kernels, which visit
    the non-empty groups only; the expert weights are read where they
    lie (no copy of them among the program's temporaries)."""
    from dlrover_tpu.models.lfm2_moe import Lfm2MoeConfig
    from dlrover_tpu.models.moe import MoeLayer
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    layer = MoeLayer(Lfm2MoeConfig().moe_sizes)
    shape = (16, 1, 2048) if tokens == 16 else (1, 1024, 2048)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(shape, jnp.bfloat16))["params"])
    held = jax.tree.map(  # as the engine holds them: the matrices in bf16
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim == 3 else a.dtype, sharding=one_chip),
        params,
    )
    compiled = jax.jit(
        lambda p, x: layer.apply({"params": p}, x, mutable=("metrics",))
    ).lower(held, x).compile()
    assert _kernel_text(compiled).count("tpu_custom_call") >= 3
    weights = 3 * 64 * 2048 * 1536 * 2
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes >= weights
    assert m.temp_size_in_bytes < weights // 8


def _lowered_step(model, loss_fn, tx, mesh, tokens, **step_options):
    """(lowered train step, described state) over a mesh of described
    devices: the state and the batch as shapes with the step's shardings."""
    from dlrover_tpu.parallel.sharding import DEFAULT_RULES, data_sharding_for
    from dlrover_tpu.parallel.train_step import build_train_step, state_shardings

    abstract, shardings = state_shardings(model, tokens, mesh, tx)
    step_fn = build_train_step(model, tx, loss_fn, mesh, shardings, **step_options)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract,
        shardings,
    )
    data = jax.ShapeDtypeStruct(
        tokens.shape,
        tokens.dtype,
        sharding=data_sharding_for(tokens, mesh, DEFAULT_RULES),
    )
    return step_fn.lower(state, data, data), state


def _gpt2_small_step(devices, mesh_config, batch=32):
    """(lowered step, state) the GPT-2-small train step exactly as
    ``chip_smoke.py``'s worker builds it, over described devices."""
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.layers import cross_entropy_loss
    from dlrover_tpu.parallel.mesh import build_mesh
    from dlrover_tpu.parallel.train_step import default_optimizer

    cfg = dataclasses.replace(GPTConfig.gpt2_small(), attention_impl="flash")
    return _lowered_step(
        GPT(cfg), cross_entropy_loss, default_optimizer(), build_mesh(mesh_config, devices),
        jnp.zeros((batch, cfg.max_seq_len), jnp.int32),
    )


def _zeros_as_held(model):
    """Zeros in the shapes of the model's own init and the dtypes a serving
    engine holds them in: it takes them as they are."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    return jax.tree.map(
        lambda a, dtype: jnp.zeros(a.shape, dtype),
        shapes, model.consumed_param_dtypes(shapes),
    )


def _prompt_row(width, sharding):
    """``prefill_row``'s tokens and mask for one ``width``-wide prompt."""
    return (
        jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((1, width), jnp.bool_, sharding=sharding),
    )


def _described(tree, sharding, rows=None):
    """The arrays of ``tree`` as shapes on a described device; with
    ``rows``, a serving engine's state for that many slots (each of its
    arrays has the slots first)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            (rows,) + a.shape[1:] if rows and a.ndim else a.shape,
            a.dtype,
            sharding=sharding,
        ),
        tree,
    )


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def test_gpt2_small_train_step_fits_one_v5e(topo, on_chip_kernels):
    from dlrover_tpu.parallel.mesh import MeshConfig

    lowered, _ = _gpt2_small_step(topo.devices[:1], MeshConfig(dp=-1))
    compiled = lowered.compile()
    _kernel_text(compiled)
    used = _device_bytes(compiled)
    # 14.7 GiB when this was written: the step fits, but NOT beside a
    # second copy of its 1.4 GiB state — which is why the checkpoint
    # engine checks the device's headroom before an async snapshot
    # (CheckpointEngine._snapshot_fits; on the chip the program reserved
    # 13.25 GiB and the step failed to load beside a snapshot)
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"


def test_gpt2_small_train_step_on_four_v5e(topo, on_chip_kernels):
    from dlrover_tpu.parallel.mesh import choose_mesh_shape

    lowered, state = _gpt2_small_step(topo.devices, choose_mesh_shape(4))
    compiled = lowered.compile()
    text = _kernel_text(compiled)
    # parameters are sharded over fsdp: the step must gather them and
    # reduce the gradients across the four chips
    assert "all-gather" in text
    assert "all-reduce" in text or "reduce-scatter" in text
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    # every parameter above a trivial size really is split four ways
    big = [
        leaf
        for leaf in jax.tree.leaves(state.params)
        if np.prod(leaf.shape) >= 1 << 16
    ]
    assert big and all(
        len(leaf.sharding.device_set) == 4
        and not leaf.sharding.is_fully_replicated
        for leaf in big
    )


def test_serving_prefill_and_decode_chunk_compile_for_v5e(
    one_chip, no_persistent_cache
):
    """The engine's two hot programs at GPT-2-small widths, 16 slots —
    the shapes ``chip_smoke.py``'s serve phase runs."""
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    cfg = dataclasses.replace(GPTConfig.gpt2_small(), use_remat=False)
    model = GPT(cfg)
    # the engine rounds what it is given, so it is given arrays: zeros
    # in the shapes of the model's own init
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        ),
    )
    engine = ContinuousBatchingEngine(
        model,
        params,
        SamplingConfig(max_new_tokens=64, temperature=0.0),
        batch_size=16,
        prompt_width=64,
        decode_chunk=8,
    )

    row = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 64), jnp.bool_, sharding=one_chip)
    held = _described(engine.params, one_chip)  # the tree the programs get
    prefill = engine._prefill_fn.lower(held, row, mask).compile()
    chunk = (
        engine._chunk_for(engine.d)
        .lower(
            held,
            _described(engine._state, one_chip),
            _described(jax.random.PRNGKey(0), one_chip),
        )
        .compile()
    )
    for compiled in (prefill, chunk):
        assert _device_bytes(compiled) < V5E_HBM_BYTES
    # 16 slots of full-length KV plus the weights: what stays resident
    assert chunk.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES // 2
    # the matrices arrive rounded: no program turns a float32 embedding
    # into a bf16 one before its first token
    for compiled in (prefill, chunk):
        assert "f32[50304,768]" not in compiled.as_text()


def test_xl_decode_chunk_carries_the_folded_cache_for_v5e(
    one_chip, no_persistent_cache, request
):
    """The engine's chunk program (8 steps) at GPT-2 XL's widths, 4 slots,
    prompt width 512, 128 new tokens: what ``gpt2xl-serve-closed`` runs.
    The cache's leaves are ``[4, 1024, 1664]`` (25 heads of 64 side by
    side in the lanes), so the loop's carry holds them at 1.04 times their
    content and not, as ``[4, 1024, 25, 64]`` did, at 2.56 times: the
    temporaries were 3.49 GB, nearly all of it that padded copy. Under
    ``pytest -s`` the same program over 8 and 16 rows is compiled too and
    the sizes printed for ``PERF.md``'s slots table (not judged: whether
    they fit is a cell's to say; not compiled where nobody would read
    them: 17 s of every core each)."""
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    model = GPT(dataclasses.replace(GPTConfig.gpt2_xl(), use_remat=False))
    params = _zeros_as_held(model)  # 3.1 GB of host memory
    slots = 4
    engine = ContinuousBatchingEngine(
        model,
        params,
        SamplingConfig(max_new_tokens=128, temperature=0.0),
        batch_size=slots,
        prompt_width=512,
        decode_chunk=8,
    )
    leaves = [a for a in jax.tree.leaves(engine._state[0]) if a.ndim > 0]
    assert len(leaves) == 2 * 48
    assert all(a.shape == (slots, 1024, 1664) for a in leaves)

    def compiled_for(rows):
        return (
            engine._chunk_for(8)
            .lower(
                _described(engine.params, one_chip),
                _described(engine._state, one_chip, rows),
                _described(jax.random.PRNGKey(0), one_chip),
            )
            .compile()
        )

    chunk = compiled_for(slots)
    memory = chunk.memory_analysis()
    assert _device_bytes(chunk) < V5E_HBM_BYTES
    assert memory.temp_size_in_bytes < 1.6e9, memory
    text = chunk.as_text()
    assert "bf16[4,1024,1664]" in text
    assert "[4,1024,25,64]" not in text

    def sizes(m):
        return (
            f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, output "
            f"{m.output_size_in_bytes / 1e9:.3f}, temporaries "
            f"{m.temp_size_in_bytes / 1e9:.3f}"
        )

    if request.config.getoption("capture") != "no":
        return
    print(f"\nXL decode chunk, described v5e, {slots} slots: {sizes(memory)}")
    for rows in (8, 16):
        try:
            print(f"{rows} slots: {sizes(compiled_for(rows).memory_analysis())}")
        except jax.errors.JaxRuntimeError as e:  # refused: a finding too
            print(f"{rows} slots: refused: {str(e)[:300]}")


# -- the programs the causal walk must not touch (PR 41) ---------------------
#
# sha256 of the parent's lowered text (commit da72202, before the kernel file
# was touched; ``jax_traceback_in_locations_limit`` 0, because a Mosaic
# kernel's body carries the file's line numbers and the general kernels moved
# down the file), for a described v5e. A call outside the walk, and every
# one-tile forward, lowers to the parent's program, text for text. A later PR
# that changes these programs on purpose takes the hashes anew and says so.
PARENT_PROGRAMS = {
    # the forward at one tile a head: the XL server's prompt width, and the
    # GPT-2 training cells' forward (their canary's bits)
    "fwd_b1_t512_h25": "1b2f6ba62b549f04b938b03420a25094b9696ff557077edb11481b07e1c9e3dd",
    "fwd_b32_t1024_h12": "1be2ea73825ee394a05ef3180e56dbd628f5bfcb1316fcae9d0beca315cccba4",
    # forward and backward outside the walk: a ragged T, t_q != t_kv (ring)
    # (taken anew in PR 44: ``_fa_fwd`` names its two results, and the second
    # ``name`` equation takes one number from the module's symbol table, so
    # the private functions lowered after it are ``@_pad_6``, ``@_pad_7`` where
    # the parent's were ``@_pad_5``, ``@_pad_6``: the operations are the
    # parent's, ``test_the_flash_results_names_emit_no_operation``)
    "fwd_bwd_ragged_t100": "c2bb5925565e06c8ec4791170a30fda7a611cfa44fc4dcddea263e9c20c65507",
    "fwd_bwd_tq256_tkv512": "b74362bb21000cade758ef5ffd373724602c57312c772ae291387fdb0629bc79",
    # the XL server's 512-wide ``prefill_row`` (855,523 characters and no
    # kernel: a prefill runs in decode mode over the cache, dense)
    "xl_prefill_row_512": "0bf1b673e1f4f51d2ef0e410dc97ce4f1e3f46286f1ef95a44217fa4ba6e12ad",
    # the causal walk with no window, forward and backward, where a head is
    # several tiles (taken from commit 7399eb8 in PR 58, before a window's
    # band became the walked kernels' grid: with ``band == 0`` the grid stays
    # ``(heads, tiles, tiles)`` and these stay): ``joyai``'s call as
    # ``mla.attend`` makes it (4 tiles a head, q/k 192 wide, v 128) and
    # ``mellum2``'s full layer (8 tiles a head)
    "walk_fwd_bwd_b4_t4096_h32_d192_dv128": "8c3c93eeef99f28e3b3ebaae7e201557d4cde91ecb6e8161b48e2ef889248d18",
    "walk_fwd_bwd_b2_t8192_h32_d128": "e5d9528a7560a771b2eef4b229181c18d96cb33accbeb25a8757299d90cabff2",
}


# the parent's (commit 623ba44) hash of the one program above whose text PR 44's
# two names renumber; it still lowers to this with the names taken out
UNNAMED_PROGRAMS = {
    "fwd_bwd_ragged_t100": "890fdc293626c14d24812f24b29cd4a7f8e3919d749dcf40195cf2d71e2f187e",
}


@pytest.fixture()
def no_locations():
    prev = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.clear_caches()  # a program traced before carries its locations
    yield
    jax.config.update("jax_traceback_in_locations_limit", prev)


def _sha256(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("program", [n for n in PARENT_PROGRAMS if n.startswith("fwd")])
def test_calls_outside_the_walk_lower_to_the_parents_program(
    program, one_chip, on_chip_kernels, no_locations
):
    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    q, kv = {
        "fwd_b1_t512_h25": (shaped(1, 512, 25, 64),) * 2,
        "fwd_b32_t1024_h12": (shaped(32, 1024, 12, 64),) * 2,
        "fwd_bwd_ragged_t100": (shaped(2, 100, 12, 64),) * 2,
        "fwd_bwd_tq256_tkv512": (shaped(2, 256, 12, 64), shaped(2, 512, 12, 64)),
    }[program]

    fn = fwd_bwd if program.startswith("fwd_bwd") else fwd
    text = jax.jit(fn).lower(q, kv, kv).as_text()
    assert "_walk_" not in text
    assert _sha256(text) == PARENT_PROGRAMS[program]


@pytest.mark.parametrize("window", [None, 8192], ids=["no_window", "window_cuts_nothing"])
@pytest.mark.parametrize("program", [n for n in PARENT_PROGRAMS if n.startswith("walk")])
def test_the_causal_walk_with_no_band_lowers_to_the_parents_program(
    program, window, one_chip, on_chip_kernels, no_locations
):
    """The walked kernels take their grid from the window's band (PR 58).
    With no window, and with a window that cuts nothing off (T or more keys),
    the band is 0 and the three kernels of each call are the parent's, text
    for text, Mosaic bodies included."""
    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    q, k, v = {
        "walk_fwd_bwd_b4_t4096_h32_d192_dv128": (
            shaped(4, 4096, 32, 192), shaped(4, 4096, 32, 192), shaped(4, 4096, 32, 128)),
        "walk_fwd_bwd_b2_t8192_h32_d128": (shaped(2, 8192, 32, 128),) * 3,
    }[program]

    def fwd(q, k, v):  # the text carries the functions' names: the pinned ones'
        return fa.flash_attention(q, k, v, causal=True, window=window)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    text = jax.jit(fwd_bwd).lower(q, k, v).as_text()
    assert text.count("_walk_") >= 3 and text.count("tpu_custom_call") == 3
    assert _sha256(text) == PARENT_PROGRAMS[program]


def test_the_flash_results_names_emit_no_operation(
    one_chip, on_chip_kernels, no_locations, monkeypatch
):
    """``_fa_fwd`` names ``out`` and ``lse`` for a caller's remat policy. A
    ``name`` equation lowers to nothing, but each distinct one is first a
    private function ``@name`` in the module's symbol table, and the second
    takes a number from the table's one counter: private functions lowered
    later (``jnp.pad``'s, at a ragged T) carry a number one higher. With
    those numbers struck out the text is the parent's, and with the names
    taken out it is the parent's to the character."""
    import re

    x = jax.ShapeDtypeStruct((2, 100, 12, 64), jnp.bfloat16, sharding=one_chip)
    named = jax.jit(fwd_bwd).lower(x, x, x).as_text()
    monkeypatch.setattr(fa, "checkpoint_name", lambda value, name: value)
    jax.clear_caches()
    unnamed = jax.jit(fwd_bwd).lower(x, x, x).as_text()
    assert _sha256(unnamed) == UNNAMED_PROGRAMS["fwd_bwd_ragged_t100"]
    assert named != unnamed

    def unnumbered(text):
        return re.sub(r"@(_pad)_\d+", r"@\1_N", text)

    assert unnumbered(named) == unnumbered(unnamed)


def test_xl_servers_prefill_is_the_parents_program(
    one_chip, on_chip_kernels, no_locations
):
    """``gpt2xl-serve-closed``'s widest prefill, built as the cell builds it
    (``attention_impl: flash``): the parent's text, and no kernel in it."""
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    model = GPT(dataclasses.replace(
        GPTConfig.gpt2_xl(), use_remat=False, attention_impl="flash"))
    params = _zeros_as_held(model)
    engine = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=128, temperature=0.0),
        batch_size=4, prompt_width=512, decode_chunk=8,
    )
    text = engine._prefill_fn.lower(
        _described(engine.params, one_chip), *_prompt_row(512, one_chip)
    ).as_text()
    assert "tpu_custom_call" not in text
    assert _sha256(text) == PARENT_PROGRAMS["xl_prefill_row_512"]


@pytest.mark.parametrize("config,layers,width,new_tokens,kernels", [
    # the first attention layer's period: conv, conv, attention (an expert
    # layer: its grouped products are megablox kernels, named "kernel")
    ("lfm2-24b-a2b-l10", 3, 1024, 512, {"kernel"}),
    # five Mamba-2 layers and the first attention layer
    ("granite-4.0-h-micro", 6, 512, 256, set()),
])
def test_served_state_families_build_no_flash_kernel(
    config, layers, width, new_tokens, kernels, one_chip, no_persistent_cache,
    monkeypatch,
):
    """``lfm2-moe-serve-rollout-16`` and ``granite-h-micro-serve-chat``: the
    servers built from the benchmark's configurations (every key and width
    as the file gives it, the depth cut to the first layers that hold one of
    each kind, which is what a test can hold in memory) construct no flash
    kernel, and their prefill and chunk programs hold none: a server runs
    the model's decode pass, which attends over the cache and returns before
    ``attention_impl`` is read (the full-size entries leave it at its
    default, ``flash``; ``dense`` is the rehearsal's). Since PR 53 neither
    family imports ``models/mla_moe.py`` (their SwiGLU and ``MoeLayer`` are
    ``models/layers.py``'s and ``models/moe.py``'s): the kernels' module is
    imported only where a non-decode pass is traced, as the init's is."""
    import json
    import os
    import re

    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine
    from dlrover_tpu.observability import spans
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        entry = json.load(f)["model"]
    cut = dict(entry["config"], num_hidden_layers=layers)
    cut["layer_types"] = cut["layer_types"][:layers]
    assert len(set(cut["layer_types"])) == 2

    def kernels_built():
        stat = spans.process_accumulator().stats().get("flash.kernel_built")
        return stat.count if stat else 0

    model, _ = build_model({"family": entry["family"], "config": cut})
    assert model.config.attention_impl == "flash"
    # the init's own forward is the non-decode pass: over its 8 tokens it
    # traces one general kernel, whose output is dead in the init program
    params = _zeros_as_held(model)
    built_before = kernels_built()
    engine = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=new_tokens, temperature=0.0),
        batch_size=16, prompt_width=width, decode_chunk=8,
    )
    held = _described(engine.params, one_chip)
    prefill = engine._prefill_fn.lower(held, *_prompt_row(width, one_chip)).as_text()
    chunk = engine._chunk_for(8).lower(
        held, _described(engine._state, one_chip),
        _described(jax.random.PRNGKey(0), one_chip),
    ).as_text()
    for text in (prefill, chunk):
        assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == kernels
        assert ("tpu_custom_call" in text) == bool(kernels)
    assert kernels_built() == built_before


def test_ssd_scan_and_step_compile_at_the_published_widths(
    one_chip, no_persistent_cache
):
    """The chunked scan over a 512-wide prefill of one row and the
    one-token step for 32 rows at Granite 4.0-H Micro's widths (64 heads of
    64, state 128, chunks of 256): block products XLA compiles, no kernel.
    The step's program holds the state as argument and as output and next
    to nothing else: one pass in, one out."""
    from dlrover_tpu.ops.ssd_scan import ssd_scan, ssd_step

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, p, n, t, rows = 64, 64, 128, 512, 32
    scan = jax.jit(ssd_scan, static_argnums=5).lower(
        shaped(1, t, heads, p, dtype=jnp.bfloat16), shaped(1, t, heads),
        shaped(heads), shaped(1, t, 1, n, dtype=jnp.bfloat16),
        shaped(1, t, 1, n, dtype=jnp.bfloat16), 256, shaped(1, heads, p, n),
    ).compile()
    assert _device_bytes(scan) < V5E_HBM_BYTES // 16
    state = rows * heads * p * n * 4
    step = jax.jit(ssd_step).lower(
        shaped(rows, heads, p, n), shaped(rows, heads, p, dtype=jnp.bfloat16),
        shaped(rows, heads), shaped(heads),
        shaped(rows, 1, n, dtype=jnp.bfloat16), shaped(rows, 1, n, dtype=jnp.bfloat16),
    ).compile()
    memory = step.memory_analysis()
    assert memory.argument_size_in_bytes < 1.02 * state
    assert memory.temp_size_in_bytes < state // 8  # no second copy of the state


def test_qwen3_next_served_programs_compile_for_v5e(
    one_chip, no_persistent_cache, monkeypatch, request
):
    """``qwen3next-serve-rag-16``: the server built from the benchmark's
    configuration (every key and width as the file gives it: 128 of 512
    experts held, the router 512 wide), cut to the first period of its
    layer pattern (delta, delta, delta, attention), 16 slots, prompts 2,048
    wide, 512 new tokens. The widest prefill and the chunk fit; the
    grouped products are megablox kernels and nothing else is one (the
    chunked delta rule and its one-token step are block products XLA
    compiles); the chunk's carry holds every slot's matrix state in
    float32. Under ``pytest -s`` all 12 layers are compiled (10.9 GB of
    zeros on the host, minutes of every core) and the sizes of the init, the
    three prefill widths, the chunk and ``admit_many`` printed for
    ``PERF.md`` section 4 (not judged here: the cell's own run is)."""
    import re
    import time

    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    entry = _benchmark_model_entry("qwen3-next-80b-a3b-ep4-l12")
    slots, width, new_tokens = 16, 2048, 512

    def served(layers):
        model, _ = build_model({"family": entry["family"],
                                "config": dict(entry["config"], num_hidden_layers=layers)})
        engine = ContinuousBatchingEngine(
            model, _zeros_as_held(model), SamplingConfig(max_new_tokens=new_tokens, temperature=0.0),
            batch_size=slots, prompt_width=width, decode_chunk=8)
        return model, engine, _described(engine.params, one_chip)

    def chunk_of(engine, held):
        return engine._chunk_for(8).lower(
            held, _described(engine._state, one_chip), _described(jax.random.PRNGKey(0), one_chip))

    _, engine, held = served(4)
    state = [a for a in jax.tree.leaves(engine._state[0]) if a.shape[1:] == (32, 128, 128)]
    assert len(state) == 3 and all(a.dtype == jnp.float32 and a.shape[0] == slots for a in state)
    prefill = engine._prefill_fn.lower(held, *_prompt_row(width, one_chip))
    chunk = chunk_of(engine, held)
    for lowered, scopes in ((prefill, ("gdn.chunk", "qwen3next.attend", "moe.shared_gate")),
                            (chunk, ("gdn.step", "gdn.gate_norm", "moe.shared_gate"))):
        text = lowered.as_text(debug_info=True)
        assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == {"kernel"}
        assert all(scope in text for scope in scopes)
        assert _device_bytes(lowered.compile()) < V5E_HBM_BYTES

    if request.config.getoption("capture") != "no":
        return

    def sizes(lowered):
        t0 = time.time()
        m = lowered.compile().memory_analysis()
        return (f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, output {m.output_size_in_bytes / 1e9:.3f}, "
                f"temporaries {m.temp_size_in_bytes / 1e9:.3f}, aliased {m.alias_size_in_bytes / 1e9:.3f} "
                f"(compiled in {time.time() - t0:.0f} s)")

    model, engine, held = served(entry["config"]["num_hidden_layers"])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def init(k):  # ``init_params_as_consumed``'s program: drawn in float32, rounded inside
        params = model.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree.map(lambda leaf, dt: leaf.astype(dt), params, model.consumed_param_dtypes(params))

    print(f"\nqwen3-next-80b-a3b-ep4-l12, described v5e, {slots} slots")
    print("init:", sizes(jax.jit(init).lower(key)))
    for w in (width // 4, width // 2, width):
        print(f"prefill_row {w}:", sizes(engine._prefill_fn.lower(held, *_prompt_row(w, one_chip))))
    print("chunk of 8 steps:", sizes(chunk_of(engine, held)))
    row = jax.eval_shape(engine._prefill_fn, engine.params, *(
        jnp.zeros((1, width // 4), dtype) for dtype in (jnp.int32, jnp.bool_)))
    row = _described(row + (jax.ShapeDtypeStruct((model.config.vocab_size,), jnp.bool_),), one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    for k in (1, 8):
        print(f"admit_many of {k}:", sizes(engine._admit_many_fn.lower(
            _described(engine._state, one_chip), (row,) * k, (i32,) * k, (i32,) * k, (i32,) * k)))


# sha256 of the lowered text of the programs of the families that share
# ``MoeLayer``, ``cached_decode_attention``, ``real_neighbours`` and the
# engine: a PR that does not mean to change them finds them here, text for
# text. The granite pair is commit 452bf4c's (before ``MoeSizes`` grew a score
# function and a shared gate and ``_masked_attention`` an unprojected return
# for the ``qwen3_next`` family) and has held since. **Every program with a
# ``MoeLayer`` in it was taken anew in PR 57, on purpose**: the layer moves
# its rows without the mask pass on the way in, reads the chosen scores from
# the top-k and carries gates and index vectors along its sorts
# (``test_mellum_cells_expert_layer_touches_its_row_buffer_once_a_move`` says
# what the new text must and must not hold; the values are the parent's:
# ``tests/test_serving_state_cache.py: PARENT_STREAMS``, ``tests/test_mla_moe.py``).
# Their parents' (57f0678): lfm2 prefill 71d26a6e..., chunk 4790e7e8...; joyai
# 9402bb6e... and, nothing kept, 3f49fce6... (623ba44's own); qwen3-next prefill
# f9c8a75c..., chunk 44ebf5c3... (623ba44's, held again in PR 48: a served share
# sets no ``train_gates``, so its buffer stays 4x the mean load, ``N x K`` rows,
# and no overflow branch enters its programs: still so).
# **PR 60 retook the two ``.prefill`` pins and joyai's two at b1 x 1024, on
# purpose** (the grouped product's row tile follows the rows a group can hold,
# ``ops/grouped_matmul.py: megablox_tiling``: 128 where it was 512 at the
# prefills' 40-64 rows a group, and at the 2,048 rows over 16 experts, 128 a
# group, of joyai's *test* shape; their parents' (9d0afa0): lfm2 prefill
# 3c9a96d2..., qwen3-next prefill 35056d2c..., joyai 5294ed1a... and
# 5844f3ee...). **Untouched, and the proof that these programs bypass the
# change**: the two ``.chunk`` pins (row tiles 64 and 160: no tile divides
# their ``m``), granite's two (no grouped product), and joyai's
# ``.b4x4096``, new in PR 60: the same two layers at the *cell's* batch,
# 32,768 rows over 16 experts, tile 512 as before, whose pin was taken on the
# parent and holds on the change.
PARENT_FAMILY_PROGRAMS = {
    "lfm2-24b-a2b-l10.prefill": "e75e4ad53c2254cf0381e4848dcb77956654441d01df7e41a99d0b802bceddac",
    "lfm2-24b-a2b-l10.chunk": "3c1902029dfdc270ad6a8f1e4776176b65ff7ae3861f7d1601c99488ab568d5c",
    "granite-4.0-h-micro.prefill": "d7b20c70771f80c106e7e7b5c264e7c5967ccbc440143b6479609c3e11e8fa38",
    "granite-4.0-h-micro.chunk": "53441fb221dd45af451d27b8513c5653328d326284c25b0f283ef47bc9ac2ce1",
    # the blocks keep their flash kernel's two results (PR 44); the second is
    # the same model with the block's policy set back to keeping nothing
    "joyai-llm-flash-ep16.loss_and_grads": "9adb5bee0f9b5fb15b004725083fb907dd86a9353542b491f619839172e87d0f",
    "joyai-llm-flash-ep16.loss_and_grads.nothing_kept": "4615c3a9fae914a09843c16617ce8683c66b5a816f9c43a2ca4abba068b7ffa7",
    "joyai-llm-flash-ep16.loss_and_grads.b4x4096": "e6592fbda63d7b2d7ddf0ea43f2ca31ad364e045f31eb80fd01385484fefe1f5",
    "qwen3-next-80b-a3b-ep4-l12.prefill": "e410ccfe26f21edb9f22d6493e207583de1a75d8ef5d7473a0591c15d68d4115",
    "qwen3-next-80b-a3b-ep4-l12.chunk": "7ae41b1230ae0b24733d230497ee8c6a964e0fcca34d76e14ad8146f864bd913",
}


def _benchmark_model_entry(config):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("config,layers,width,new_tokens,gmm_tiles", [
    ("lfm2-24b-a2b-l10", 3, 1024, 512, ({(4096, 64, 128)}, {(64, 64, 64)})),
    ("granite-4.0-h-micro", 6, 512, 256, (set(), set())),
    # the first period of its pattern (delta, delta, delta, attention), as
    # ``test_qwen3_next_served_programs_compile_for_v5e`` cuts it
    ("qwen3-next-80b-a3b-ep4-l12", 4, 2048, 512, ({(20480, 128, 128)}, {(160, 128, 160)})),
], ids=lambda v: "tiles" if isinstance(v, tuple) else str(v))
def test_served_families_programs_are_the_parents(
    config, layers, width, new_tokens, gmm_tiles, one_chip, no_persistent_cache, no_locations, monkeypatch
):
    """``lfm2-moe-serve-rollout-16`` and ``granite-h-micro-serve-chat``: the
    widest prefill and the chunk of the servers built from the benchmark's
    configurations (the depth cut as in the test above), lowered for the
    described chip: the pinned text (granite's the parent's still; the two
    with a ``MoeLayer`` taken anew in PR 57, and their prefills again in PR
    60, whose grouped products run 128 rows a tile where they ran 512; the
    chunks, at a row tile of their whole ``m``, did not move)."""
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    entry = _benchmark_model_entry(config)
    cut = dict(entry["config"], num_hidden_layers=layers)
    if "layer_types" in cut:  # (``qwen3_next`` derives its pattern from an interval)
        cut["layer_types"] = cut["layer_types"][:layers]
    model, _ = build_model({"family": entry["family"], "config": cut})
    engine = ContinuousBatchingEngine(
        model, _zeros_as_held(model), SamplingConfig(max_new_tokens=new_tokens, temperature=0.0),
        batch_size=16, prompt_width=width, decode_chunk=8,
    )
    held = _described(engine.params, one_chip)
    prefill = engine._prefill_fn.lower(held, *_prompt_row(width, one_chip)).as_text()
    chunk = engine._chunk_for(8).lower(
        held, _described(engine._state, one_chip),
        _described(jax.random.PRNGKey(0), one_chip),
    ).as_text()
    # (rows, experts held, row tile) of the grouped products, a prefill's and a chunk's
    assert (_gmm_row_tiles(prefill), _gmm_row_tiles(chunk)) == gmm_tiles
    got = {f"{config}.prefill": _sha256(prefill), f"{config}.chunk": _sha256(chunk)}
    assert got == {name: PARENT_FAMILY_PROGRAMS.get(name) for name in got}, got


@pytest.mark.parametrize("kept,batch,row_tile,flash_kernels,pin", [
    # a block: forward, dk/dv, dq, and under the parent's policy the forward again
    ("flash_results", (1, 1024), 128, {"_fwd_kernel": 3, "_walk_bwd_kernel": 6}, "joyai-llm-flash-ep16.loss_and_grads"),
    ("nothing", (1, 1024), 128, {"_fwd_kernel": 6, "_walk_bwd_kernel": 6}, "joyai-llm-flash-ep16.loss_and_grads.nothing_kept"),
    # the cell's own batch (PR 60): 32,768 rows over 16 experts keep 512 rows a tile, and the parent's text
    # (beyond one tile a head the forward walks too: PR 41)
    ("flash_results", (4, 4096), 512, {"_walk_fwd_kernel": 3, "_walk_bwd_kernel": 6}, "joyai-llm-flash-ep16.loss_and_grads.b4x4096"),
])
def test_trained_moe_models_blocks_keep_their_flash_kernels_results(
    kept, batch, row_tile, flash_kernels, pin, one_chip, on_chip_kernels, no_locations, monkeypatch
):
    """``joyai-flash-train-ep16share``: the model built from the benchmark's
    configuration (two layers: the dense one and the first expert layer,
    with its MTP module: three blocks), its losses and their gradients over
    b1 x 1024 (and over the cell's b4 x 4096), lowered for the described chip. Each rematerialised block
    keeps its flash kernel's ``out`` and ``lse`` (PR 44), so the text holds
    one flash ``tpu_custom_call`` a block fewer than the parent's: 9, not 12,
    and nothing else but the kernel's four transposes a block and one
    ``reduce_precision`` on the kept ``out``. With the block's policy set
    back to ``nothing_saveable`` and the two names taken out of ``_fa_fwd``
    (they emit no operation, but number later private functions one higher)
    the text was PR 42's to the character until PR 57 changed ``MoeLayer``'s
    part of both on purpose; at b1 x 1024 an expert's group holds 128 rows
    and the grouped products' row tile follows it since PR 60, whose pins
    these two are; the third, at the cell's batch, is the parent's."""
    import collections
    import re

    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    if kept == "nothing":  # the parent's blocks, and no names in ``_fa_fwd``
        monkeypatch.setattr(mla_moe, "KEEP_FLASH_RESULTS", jax.checkpoint_policies.nothing_saveable)
        monkeypatch.setattr(fa, "checkpoint_name", lambda value, name: value)
    entry = _benchmark_model_entry("joyai-llm-flash-ep16")
    model, loss_fn = build_model(
        {"family": entry["family"], "config": dict(entry["config"], num_hidden_layers=2)})
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def loss_and_grads(params, tokens, targets):
        def loss(p):
            losses, sown = model.apply({"params": p}, tokens, targets=targets, mutable=("objective", "metrics"))
            return loss_fn(losses, targets) + sum(jax.tree.leaves(sown["objective"]))

        return jax.value_and_grad(loss)(params)

    text = jax.jit(loss_and_grads).lower(_described(params, one_chip), tokens, tokens).as_text()
    names = collections.Counter(re.findall(r'kernel_name = "([^"]+)"', text))
    # megablox's, the same on both sides: 13 until PR 57, whose gates go to their
    # rows along the layer's sort and come back along it (the collecting kernel
    # over ``f32[rows, 256]`` behind the spread gates is gone)
    assert names.pop("kernel") == 12
    assert names == flash_kernels
    rows = batch[0] * batch[1] * 2  # 4 x the mean load of the 16 experts held: 8 of 256 a token
    assert _gmm_row_tiles(text) == {(rows, 16, row_tile)}
    # (on the kept ``out``, bf16; the expert layers' float32 gates are rounded by the same operation since PR 57)
    assert len(re.findall(r"stablehlo\.reduce_precision.*xbf16>", text)) == (3 if kept == "flash_results" else 0)
    assert _sha256(text) == PARENT_FAMILY_PROGRAMS[pin], _sha256(text)


def test_joyai_cells_whole_step_fits_beside_the_kept_flash_results(
    topo, on_chip_kernels, monkeypatch, request
):
    """``joyai-flash-train-ep16share``'s train step as its worker builds it
    (5 layers and the MTP module, b4 x 4096, the optimizer, the counters),
    compiled for the described chip: six blocks each keep ``out
    bf16[4,4096,32,128]`` and ``lse f32[128,4096]`` from the forward pass to
    their turn in the backward pass, 136 MB a block, and arguments and
    temporaries together stay under 15.0 GiB of the chip's 15.75. When this
    was written: 7.605 + 6.029 = 13.634 GiB, against 7.605 + 6.858 = 14.463
    with nothing kept (``pytest -s`` compiles that side too and prints
    both): the compiler's temporaries *fall* by 0.83 GiB where the kept
    values alone would add 0.76, because the rematerialised block no longer
    holds the forward kernel's operands and result beside the backward's."""
    import re

    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.ops import grouped_matmul as gm
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.train_step import default_optimizer

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    entry = _benchmark_model_entry("joyai-llm-flash-ep16")
    mesh = build_mesh(MeshConfig(dp=-1), topo.devices[:1])

    def sizes():
        """(arguments + temporaries, flash kernel calls, both in words) of the step compiled now."""
        model, loss_fn = build_model(entry)
        lowered, _ = _lowered_step(
            model, loss_fn, default_optimizer(learning_rate=1e-3, warmup_steps=2), mesh,
            jnp.zeros((4, 4096), jnp.int32), return_metrics=True)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        held = m.argument_size_in_bytes + m.temp_size_in_bytes
        flash = len(re.findall(r'mla\.attend\.\d+ = .*custom_call_target="tpu_custom_call"', compiled.as_text()))
        return held, flash, (f"arguments {m.argument_size_in_bytes / 2**30:.3f} GiB + temporaries "
                             f"{m.temp_size_in_bytes / 2**30:.3f} = {held / 2**30:.3f} GiB (output "
                             f"{m.output_size_in_bytes / 2**30:.3f}, aliased {m.alias_size_in_bytes / 2**30:.3f}), "
                             f"{flash} flash kernel calls")

    held, flash, line = sizes()
    assert flash == 18, line  # 6 blocks x forward, dk/dv, dq
    assert held < 15.0 * 2**30, line
    if request.config.getoption("capture") != "no":
        return
    print(f"\njoyai step, described v5e, b4 x 4096, out and lse kept: {line}")
    monkeypatch.setattr(mla_moe, "KEEP_FLASH_RESULTS", jax.checkpoint_policies.nothing_saveable)
    held_parent, _, line = sizes()
    print(f"nothing kept (the parent's blocks): {line}")
    print(f"the kept results cost {(held - held_parent) / 1e6:.1f} MB")


# -- the window band (PR 47) ---------------------------------------------------


@pytest.mark.parametrize("window,path,tiles_run,tiles_visited", [
    (1024, "window_tiled", 15, 16), (None, "causal_tiled", 36, 64)])
def test_windowed_flash_kernels_compile_at_the_mellum_cells_shape(
    window, path, tiles_run, tiles_visited, one_chip, on_chip_kernels
):
    """``mellum2-train-ep4share``'s attention as its layers call it, b2 x
    8192, 32 heads of 128, forward and backward, for the described chip: the
    window layers' band (2 key tiles a row tile; ``tiles_visited`` is the
    grid as built, ``band + 1`` steps a row tile: 15 of 16 a head run) and
    the full layer's causal walk at 8 tiles a head (36 of 64), a shape no
    other cell runs."""
    x = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, True, window=window).astype(jnp.float32).sum()

    from dlrover_tpu.observability import spans

    compiled = jax.jit(jax.grad(attend, (0, 1, 2))).lower(x, x, x).compile()
    assert _kernel_text(compiled).count("tpu_custom_call") >= 3
    plan = fa._kernel_plan(True, 8192, 8192, 1024, 1024, 256, window)
    assert (plan["path"], plan["tiles_run"], plan["tiles_visited"]) == (
        path, tiles_run, tiles_visited)
    assert spans.process_accumulator().stats()["flash.kernel_built"].count >= 3


def test_mellum_cells_expert_layer_touches_its_row_buffer_once_a_move(
    one_chip, no_persistent_cache, monkeypatch
):
    """``mellum2-train-ep4share``'s ``MoeLayer`` alone, forward and backward,
    at the cell's sizes (16,384 tokens x top-8 = 131,072 assignments, a buffer
    of 65,536 rows x 2,304, 16 of 64 experts held, two passes of which the
    second stands under a ``cond``), compiled for the described chip. What
    the compiled program holds, counted by instruction (PR 57):

    - nine gathers over ``bf16[65536,2304]`` (a pass moves its rows in twice
      and out twice, forward and backward, and the overflow pass's backward
      spreads once more to run its products again) and **four** selects over
      the buffer: one for each collecting kernel, on the way out, each inside
      the fusion that makes the rows it masks (the product with the gates, the
      sum of the two cotangents), so none is a pass of its own. The way in has
      none; the parent had one behind every gather, nine, each its own pass.
    - no gather whose result is ``s32[65536]`` (the parent: six, ``key[taken]``
      and ``token[by_token]``, 0.31 ms each on the chip) and none that takes
      the chosen scores ``f32[16384,8]`` out of ``f32[16384,64]``, nor a row
      of ``[rows, 64]`` gates: the index vectors come out of the sorts that
      made them, the chosen scores out of the top-k that chose them, a row's
      gate along the layer's one sort.
    - the program as traced sorts once a layer and once a pass: four ``sort``
      equations (the layer's, the first pass's, the overflow pass's in the
      forward pass and where its backward runs it again); the parent's
      ``_collect`` sorted anew in each direction, five."""
    import re

    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.moe import MoeLayer
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    model, _ = build_model(_benchmark_model_entry("mellum2-12b-a2.5b-ep4-l4"))
    sizes = model.config.moe_sizes
    assert (sizes.n_experts, sizes.experts_here, sizes.top_k, sizes.bias_name) == (64, 16, 8, "")
    layer = MoeLayer(sizes)
    shape = (2, 8192, model.config.hidden_size)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = _described(jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(shape, jnp.bfloat16))["params"]), one_chip)

    def loss_grads(p, x):
        def loss(p, x):
            out = layer.apply({"params": p}, x, mutable=("metrics",))[0]
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1))(p, x)

    def sorts(jaxpr):
        """``sort`` equations in a jaxpr and every jaxpr its equations carry."""
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "sort"
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    n += sorts(sub) if hasattr(sub, "eqns") else 0
        return n

    assert sorts(jax.make_jaxpr(loss_grads)(params, x).jaxpr) == 4
    text = jax.jit(loss_grads).lower(params, x).compile().as_text()

    def count(instruction, result):
        return len(re.findall(rf"= {re.escape(result)}\S* {instruction}\(", text))

    assert count("gather", "bf16[65536,2304]") == 9
    assert count("custom-call", "bf16[128,128,2304]") == 4  # the collecting kernel, 128 tiles of 128 tokens
    assert count("select", "bf16[65536,2304]") == 4
    selecting = [c for c in text.split("\n\n") if re.search(r"= bf16\[65536,2304\]\S* select\(", c)]
    assert len(selecting) == 4 and all(re.search(r" (multiply|add)\(", c) for c in selecting)
    assert "[131072,2304]" not in text
    assert count("gather", "s32[65536]") == 0
    assert count("gather", "f32[16384,8]") == 0  # (the parent: one, ``take_along_axis(scores, idx)``)
    assert count("gather", "f32[65536,64]") == 0  # (the parent: three, a row of gates behind every ``token_of``)
    assert len(re.findall(r" conditional\(", text)) == 2


def test_mellum_cells_whole_step_fits_one_v5e(topo, on_chip_kernels, monkeypatch, request):
    """``mellum2-train-ep4share``'s train step as its worker builds it (4
    layers, b2 x 8192, the optimizer, the counters), compiled for the
    described chip: 12 flash kernel calls (a layer's forward, dk/dv and dq;
    9 under ``swa.attend_window``, 3 under ``swa.attend_full``), and
    arguments and temporaries together under 14.5 GiB of the chip's 15.75.
    Since PR 48 a share's row buffer is twice the mean load (``MoeSizes.
    buffer_over_mean``): no value of the step has all 131,072 assignments
    x 2,304 any more, the rows move 65,536 at a time, and each layer's
    overflow pass stands under a conditional in the forward pass, in the
    block's second forward and in the backward pass. When this was written:
    6.652 + 5.205 = 11.857 GiB (the parent's 6.652 + 4.443 = 11.095; with
    the overflow branch left out 9.998, with it taken unconditionally
    10.822: the conditionals cost 1.86 GiB where the halved buffer gives
    back 1.10: ``PERF.md`` section 6, PR 48)."""
    import re

    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.ops import grouped_matmul as gm
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.train_step import default_optimizer

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    model, loss_fn = build_model(_benchmark_model_entry("mellum2-12b-a2.5b-ep4-l4"))
    mesh = build_mesh(MeshConfig(dp=-1), topo.devices[:1])
    lowered, _ = _lowered_step(
        model, loss_fn, default_optimizer(learning_rate=1e-3, warmup_steps=2), mesh,
        jnp.zeros((2, 8192), jnp.int32), return_metrics=True)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.temp_size_in_bytes
    text = compiled.as_text()
    line = (f"arguments {m.argument_size_in_bytes / 2**30:.3f} GiB + temporaries "
            f"{m.temp_size_in_bytes / 2**30:.3f} = {held / 2**30:.3f} GiB")
    kernels = {kind: len(re.findall(
        rf'swa\.attend_{kind}\.\d+ = .*custom_call_target="tpu_custom_call"', text))
        for kind in ("window", "full")}
    assert kernels == {"window": 9, "full": 3}, kernels
    assert held < 14.5 * 2**30, line
    assert "[131072,2304]" not in text and "[65536,2304]" in text
    assert len(re.findall(r" conditional\(", text)) == 3 * 4
    if request.config.getoption("capture") == "no":
        print(f"\nmellum step, described v5e, b2 x 8192: {line}")


def test_olmo_hybrid_served_programs_compile_for_v5e(
    one_chip, no_persistent_cache, on_chip_kernels, request
):
    """``olmo-hybrid-serve-longdoc-4``: the server built from the benchmark's
    configuration (every key and width as the file gives it), cut to the
    first period of its layer pattern (delta, delta, delta, attention), 4
    slots, prompts 8,192 wide over rows of 8,704 positions, 512 new tokens.
    The widest prefill fits, and it holds no ``[W, L]`` scores: the one
    kernel in it is the flash forward under ``olmo.attend_prefill`` (30 x
    8,192 x 8,704 float32 scores alone would be 8.6 GB); the chunk holds no
    kernel and carries every slot's matrix state in float32. Under ``pytest
    -s`` all 8 layers are compiled (4.9 GB of zeros on the host) and the sizes
    of the three prefill widths, the chunk and ``admit_many`` printed for
    ``PERF.md`` section 4 (not judged here: the cell's own run is)."""
    import re
    import time

    from dlrover_tpu.models import layers
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    entry = _benchmark_model_entry("olmo-hybrid-7b-pp4-l8")
    slots, width, new_tokens = 4, 8192, 512
    assert entry["config"]["max_seq_len"] == width + new_tokens
    assert all(layers.prefill_is_tiled(w, width + new_tokens) for w in (2048, 4096, 8192))

    def served(n_layers):
        model, _ = build_model({"family": entry["family"],
                                "config": dict(entry["config"], num_hidden_layers=n_layers)})
        engine = ContinuousBatchingEngine(
            model, _zeros_as_held(model), SamplingConfig(max_new_tokens=new_tokens, temperature=0.0),
            batch_size=slots, prompt_width=width, decode_chunk=8)
        return model, engine, _described(engine.params, one_chip)

    def chunk_of(engine, held):
        return engine._chunk_for(8).lower(
            held, _described(engine._state, one_chip), _described(jax.random.PRNGKey(0), one_chip))

    _, engine, held = served(4)
    state = [a for a in jax.tree.leaves(engine._state[0]) if a.shape[1:] == (30, 96, 192)]
    assert len(state) == 3 and all(a.dtype == jnp.float32 and a.shape[0] == slots for a in state)
    keys = [a for a in jax.tree.leaves(engine._state[0]) if a.shape[1:] == (width + new_tokens, 3840)]
    assert len(keys) == 2 and all(a.dtype == jnp.bfloat16 for a in keys)  # folded: 30 heads of 128 side by side
    prefill = engine._prefill_fn.lower(held, *_prompt_row(width, one_chip))
    chunk = chunk_of(engine, held)
    for lowered, scopes, kernels in (
            (prefill, ("gdn.chunk", "olmo.attend_prefill", "olmo.mlp"), True),
            (chunk, ("gdn.step", "gdn.gate_norm", "olmo.attend_decode", "olmo.mlp"), False)):
        text = lowered.as_text(debug_info=True)
        assert ("tpu_custom_call" in text) == kernels
        assert all(scope in text for scope in scopes)
        assert _device_bytes(lowered.compile()) < V5E_HBM_BYTES
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", prefill.as_text())) == 1

    if request.config.getoption("capture") != "no":
        return

    def sizes(lowered):
        t0 = time.time()
        m = lowered.compile().memory_analysis()
        return (f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, output {m.output_size_in_bytes / 1e9:.3f}, "
                f"temporaries {m.temp_size_in_bytes / 1e9:.3f}, aliased {m.alias_size_in_bytes / 1e9:.3f} "
                f"(compiled in {time.time() - t0:.0f} s)")

    model, engine, held = served(entry["config"]["num_hidden_layers"])
    print(f"\nolmo-hybrid-7b-pp4-l8, described v5e, {slots} slots")
    for w in (width // 4, width // 2, width):
        print(f"prefill_row {w}:", sizes(engine._prefill_fn.lower(held, *_prompt_row(w, one_chip))))
    print("chunk of 8 steps:", sizes(chunk_of(engine, held)))
    row = jax.eval_shape(engine._prefill_fn, engine.params, *(
        jnp.zeros((1, width // 4), dtype) for dtype in (jnp.int32, jnp.bool_)))
    row = _described(row + (jax.ShapeDtypeStruct((model.config.vocab_size,), jnp.bool_),), one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    for k in (1, 4):
        print(f"admit_many of {k}:", sizes(engine._admit_many_fn.lower(
            _described(engine._state, one_chip), (row,) * k, (i32,) * k, (i32,) * k, (i32,) * k)))


def test_sdar_moe_cells_programs_compile_for_v5e(one_chip, monkeypatch, request, no_persistent_cache):
    """``sdar-moe-serve-blockgen-16``: the server built from the benchmark's
    configuration (every key and width as the file gives it, all 128 experts,
    the whole vocabulary; two of its six layers, which is what a test can
    hold in memory) lowers its 1,024-wide prefill and its block chunk (9
    passes of 16 rows x 4 positions at per-row slots) for a described v5e,
    each with the family's scopes and the grouped products' kernel in its
    text, and both fit the chip. ``-s`` compiles all six layers and prints
    the programs' sizes (8.7 GB of zeros on the host)."""
    import re
    import time

    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    entry = _benchmark_model_entry("sdar-30b-a3b-pp8-l6")
    slots, width, new_tokens = 16, 1024, 256

    def served(layers):
        model, _ = build_model({"family": entry["family"],
                                "config": dict(entry["config"], num_hidden_layers=layers)})
        engine = ContinuousBatchingEngine(
            model, _zeros_as_held(model), SamplingConfig(max_new_tokens=new_tokens, temperature=0.0),
            batch_size=slots, prompt_width=width)
        return engine, _described(engine.params, one_chip)

    def prefill_of(engine, held, w):
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        tail = jax.ShapeDtypeStruct((engine.blocks.block_length,), jnp.int32, sharding=one_chip)
        return engine._prefill_fn.lower(held, *_prompt_row(w, one_chip), tail, i32)

    def chunk_of(engine, held):
        return engine._chunk_for(engine.d).lower(
            held, _described(engine._state, one_chip), _described(jax.random.PRNGKey(0), one_chip))

    engine, held = served(2)
    assert engine.d == 9 and engine.blocks.block_length == 4  # the default for such a model: whole blocks of 3 passes
    # (program, its scopes, the rows of its grouped products: positions x top-8)
    for lowered, scopes, rows in (
            (prefill_of(engine, held, width), ("sdar.attend_prefill", "moe.route", "moe.experts"), width * 8),
            (chunk_of(engine, held), ("sdar.attend_block", "moe.route", "moe.experts"), slots * 4 * 8)):
        text = lowered.as_text(debug_info=True)
        assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == {"kernel"}
        assert all(scope in text for scope in scopes) and "sdar.attend\"" not in text
        assert _device_bytes(lowered.compile()) < V5E_HBM_BYTES
        # 128 rows a tile since PR 60 (512 until then: a pass's 512 rows lie in groups of ~6)
        assert _gmm_row_tiles(text) == {(rows, 128, 128)}

    if request.config.getoption("capture") != "no":
        return

    def sizes(lowered):
        t0 = time.time()
        m = lowered.compile().memory_analysis()
        return (f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, output {m.output_size_in_bytes / 1e9:.3f}, "
                f"temporaries {m.temp_size_in_bytes / 1e9:.3f}, aliased {m.alias_size_in_bytes / 1e9:.3f} "
                f"(compiled in {time.time() - t0:.0f} s)")

    engine, held = served(entry["config"]["num_hidden_layers"])
    print(f"\nsdar-30b-a3b-pp8-l6, described v5e, {slots} slots")
    for w in (width // 4, width // 2, width):
        print(f"prefill_block_row {w}:", sizes(prefill_of(engine, held, w)))
    print(f"chunk of {engine.d} passes:", sizes(chunk_of(engine, held)))
