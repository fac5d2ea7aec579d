"""The chip's compiler, without the chip: programs of the main path compiled
for a DESCRIBED (not attached) v5e:2x2 by the real XLA:TPU and Mosaic
compilers.  Nothing executes; what the compiler refuses, or stops doing,
fails here at no chip time.

Every test that describes a TPU topology lives in THIS file: one process
loads libtpu at a time, so under several test workers a second file's
fixture would skip all of its tests in silence.  The topology is described
inside a fixture only, never while a module is imported.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel import collective_schedule, make_dp_train_step
from byteps_tpu.parallel.expert import dropless_moe_mlp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# ------------------------------------------------ the dropless expert layer

def _route_kernels(text):
    """The ``op_name`` of each Mosaic kernel of a compiled program's text
    under the stage ``bps.moe.route`` — after checking that NO instruction
    under that stage is a sort, a gather or a scatter (what ``lax.top_k``,
    ``take_along_axis`` and ``bincount`` compiled to, PR 41)."""
    under = [line for line in text.splitlines() if "bps.moe.route" in line]
    assert under
    slow = [line.strip()[:200] for line in under
            if re.search(r" (sort|gather|scatter)\(", line)]
    assert not slow, slow
    calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in under
             if 'custom_call_target="tpu_custom_call"' in line]
    assert all(c.endswith("/bps_moe_select/pallas_call")
               and re.findall(r"bps\.moe\.\w+", c) == ["bps.moe.route"]
               for c in calls), calls
    return calls


def test_layer_compiles_for_a_v5e_at_the_published_widths(one_chip):
    """OLMoE-1B-7B's expert layer, forward and backward, at 4 x 4096
    tokens: Mosaic takes the grouped matmuls at ``_GMM_TILE`` (two larger
    tiles overflow VMEM) — nine kernels, none interpreted or replaced — and
    the route stage's selection (``bps_moe_select``, top-8 of 64 over
    16 384 tokens): nothing under that stage sorts, gathers or scatters."""
    n, h, f, e, k = 4 * 4096, 2048, 1024, 64, 8

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"router": shaped((h, e), jnp.float32),
              "gate": shaped((e, h, f), jnp.float32),
              "up": shaped((e, h, f), jnp.float32),
              "down": shaped((e, f, h), jnp.float32)}

    def objective(params, x):
        y, aux, z, _ = dropless_moe_mlp(x, params, k, interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + aux + z

    text = jax.jit(jax.grad(objective, argnums=(0, 1))).lower(
        params, shaped((n, h), jnp.bfloat16)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9 + 1
    assert len(_route_kernels(text)) == 1
    for scope in ("bps.moe.route", "bps.moe.dispatch", "bps.moe.experts",
                  "bps.moe.combine"):
        assert scope in text


def test_held_share_compiles_for_a_v5e_at_the_published_widths(one_chip):
    """Mellum2-12B-A2.5B's expert layer as one chip of four holds it (16
    of 64 experts, renormalised top-8) at 2 x 8192 tokens: the grouped
    matmuls take a group offset (``gmm``) and a count of local groups
    (``tgmm``) — nine kernels under ``bps.moe.experts``, none interpreted
    or replaced — and the row passes that follow the live rows (a range
    that is NOT a prefix of the sorted order) are the repo's own four, each
    under its stage's scope: Mosaic takes the single-row DMAs off the
    ``[N, 1, h]`` float32 source and the 1 024-row SMEM index block.  The
    selection is the route stage's one kernel."""
    n, h, f, e, g, k = 2 * 8192, 2304, 896, 64, 16, 8

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"router": shaped((h, e), jnp.float32),
              "gate": shaped((g, h, f), jnp.float32),
              "up": shaped((g, h, f), jnp.float32),
              "down": shaped((g, f, h), jnp.float32)}

    def objective(params, x):
        y, aux, _, _ = dropless_moe_mlp(x, params, k, interpret=False,
                                        held=(16, g), renormalize=True)
        return jnp.sum(y.astype(jnp.float32)) + aux

    compiled = jax.jit(jax.grad(objective, argnums=(0, 1))).lower(
        params, shaped((n, h), jnp.bfloat16)).compile()
    # the ``down`` matmul's two gradients tied (``_tie_gradients``): 2.69
    # GiB of temp here, 2.79 where XLA may run the matrix gradient last
    # and keep the [131072, 2304] row gradient alive meanwhile (in the
    # cell's whole step that was + 0.53 GiB of ``peak_hbm_GiB``, PR 41)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.75 * 2 ** 30
    text = compiled.as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("bps.moe.experts" in c for c in calls) == 9
    own = [c for c in calls if "bps.moe.experts" not in c]
    assert sorted(
            re.search(r"(bps\.moe\.\w+)\)*/jit\(\w+\)/(\w+)/pallas_call$",
                      c).groups()
            for c in own) == [
        ("bps.moe.combine", "bps_moe_spread_scaled"),
        ("bps.moe.dispatch", "bps_moe_spread"),
        ("bps.moe.gate", "bps_moe_gate"),
        ("bps.moe.gate", "bps_moe_gate_bwd"),
        ("bps.moe.route", "bps_moe_select")], own
    assert len(_route_kernels(text)) == 1


def test_thin_held_share_compiles_for_a_v5e_in_windows(one_chip):
    """Nemotron 3 Super's routed experts as one chip of 64 holds them (8
    of 512, top-22, no gate, a latent of 1024) at 1 x 8192 tokens: 180 224
    pair rows of which ~2 816 land here, so the layer works in windows of
    6 144 rows (``window_rows``).  Mosaic takes the row kernels and the
    grouped matmuls at 6 144 rows inside loops with a runtime trip count;
    the kernels under ``bps.moe.experts`` — what ``latent_moe_ms`` sums —
    are the forward loop's two and the backward loop's two recomputed,
    two row gradients and two matrix gradients; the row kernels keep
    their stages' scopes, the token-order sum (``bps_moe_sum``, PR 49)
    under the combine forward and the dispatch backward."""
    n, h, f, e, g, k = 8192, 1024, 2688, 512, 8, 22

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"up": shaped((g, h, f), jnp.float32),
              "down": shaped((g, f, h), jnp.float32)}

    def objective(params, x, scores):
        y = dropless_moe_mlp(x, params, k, interpret=False, held=(16, g),
                             renormalize=True, routing=(scores, None))[0]
        return jnp.sum(y.astype(jnp.float32)), y

    text = jax.jit(jax.value_and_grad(
        objective, argnums=(0, 1, 2), has_aux=True)).lower(
        params, shaped((n, h), jnp.bfloat16),
        shaped((n, e), jnp.float32)).compile().as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    select = _route_kernels(text)              # top-22 of 512: one kernel
    assert len(select) == 1
    calls.remove(select[0])
    assert all("/while/body/" in c for c in calls)
    experts = [c for c in calls
               if re.search(r"bps\.moe\.experts/.*pallas_call$", c)]
    assert len(experts) == 2 + 2 + 4
    assert sum("jit(tgmm)" in c for c in experts) == 2
    own = [c for c in calls if c not in experts]
    assert sorted(
            re.search(r"(bps\.moe\.\w+)\)*/jit\(\w+\)/(\w+)/pallas_call$",
                      c).groups()
            for c in own) == [
        ("bps.moe.act", "bps_moe_act"), ("bps.moe.act", "bps_moe_act"),
        ("bps.moe.act", "bps_moe_act_bwd"),
        ("bps.moe.combine", "bps_moe_spread_scaled"),
        ("bps.moe.combine", "bps_moe_sum"),
        ("bps.moe.dispatch", "bps_moe_spread"),
        ("bps.moe.dispatch", "bps_moe_spread"),
        ("bps.moe.dispatch", "bps_moe_sum")], own
    # no array of all 180 224 pair rows is left but the sort's columns
    assert not re.search(r"\[180224,\d+\]", text)


# ---------------------------------------------------- the flash kernels

@pytest.mark.parametrize("shape,dtype,causal,kernels", [
    ((8, 1024, 16, 64), jnp.bfloat16, True, 2),    # gpt2_medium.fused_1c
    ((4, 4096, 16, 128), jnp.bfloat16, True, 2),   # olmoe_1b_7b.fused_1c
    ((1, 8192, 2, 128), jnp.bfloat16, True, 3),    # too long for VMEM
    ((2, 1000, 2, 64), jnp.bfloat16, True, 2),     # a padded key tail
    ((8, 128, 16, 64), jnp.float32, False, 2),     # one short sub-block
], ids=["gpt2_1024", "olmoe_4096", "long_8192", "ragged_1000", "short_128"])
def test_flash_kernels_compile_for_a_v5e(one_chip, shape, dtype, causal,
                                         kernels):
    """Forward and backward at both cells' shapes: Mosaic takes the
    in-kernel loops with runtime trip counts and the VMEM the resident
    side needs; one backward kernel where the q side of a head fits in
    VMEM, two where the context is too long for that."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def objective(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(objective, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


@pytest.mark.parametrize("shape,window,kernels", [
    ((2, 8192, 32, 128), 1024, 3),     # mellum2_12b.fused_1c: the long form
    ((2, 8192, 32, 128), None, 3),     # ... and its full layer
    ((4, 4096, 16, 128), 1024, 2),     # a window in the resident form
], ids=["mellum_swa_8192", "mellum_full_8192", "window_4096"])
def test_windowed_flash_kernels_compile_for_a_v5e(one_chip, shape, window,
                                                  kernels):
    """The windowed specialisation of all three kernels at the new cell's
    shape (two loop bounds from the runtime ``q_off``, one more compare a
    mask): Mosaic takes it in the long form (two-kernel backward) and in
    the resident one."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def objective(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(objective, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


# ------------------------------- the fused step's asynchronous all-reduce

def _compiled_dp_step(devices) -> str:
    """``make_dp_train_step`` (its own jit, its own compiler options) for
    an MLP of four 32 MiB leaves under AdamW, compiled for ``devices``.
    (Over the combiner's 30 MiB, so no leaf can ride a tuple.)"""
    n = len(devices)
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)
    rep = comm.replicated_sharding()

    def loss(p, b):
        h = b["x"]
        for name in sorted(p):
            h = jnp.tanh(h @ p[name])
        return jnp.mean(h ** 2)

    def shaped(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    tx = optax.adamw(1e-3)
    params = {f"w{i}": jax.ShapeDtypeStruct(
        (2048, 4096) if i % 2 == 0 else (4096, 2048), jnp.float32)
        for i in range(4)}
    batch = {"x": jax.ShapeDtypeStruct((8 * n, 2048), jnp.float32)}
    step = make_dp_train_step(comm, loss, tx)
    return step.lower(
        shaped(params, rep), shaped(jax.eval_shape(tx.init, params), rep),
        shaped(batch, NamedSharding(comm.mesh, P(comm.dp_axes)))
    ).compile().as_text()


def test_dp_step_on_four_chips_reduces_asynchronously(topo):
    """The mechanism of PR 26, end to end through the builder: a described
    chip's platform is "tpu", so the step carries
    ``ASYNC_REDUCE_COMPILER_OPTIONS`` and XLA:TPU wraps each 32 MiB
    leaf's all-reduce in an async-collective fusion.  Only the loss's
    scalar stays synchronous."""
    assert collective_schedule(_compiled_dp_step(topo.devices)) == {
        "sync": 1, "async": 4}


def test_dp_step_on_one_chip_holds_no_collective(topo):
    assert collective_schedule(_compiled_dp_step(topo.devices[:1])) == {
        "sync": 0, "async": 0}


# ------------------------------------------------- a whole cell's step

def _compiled_cell_step(topo, monkeypatch, cell):
    """``cell``'s step as ``benchmarks/paths/fused.py`` builds it
    (``make_dp_train_step`` over the family's loss, its optimizer, per-block
    ``remat``) at the published widths, compiled for ONE described chip ->
    (compiled, config, traffic).  (The models ask ``on_tpu()`` whether to
    interpret their kernels; a described chip is no backend, so the test
    answers for it.)"""
    import os
    import sys
    import byteps_tpu.ops.pallas_kernels as kernels
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    # the module, not the function ``byteps_tpu.ops`` exports under its name
    monkeypatch.setattr(sys.modules["byteps_tpu.ops.flash_attention"],
                        "on_tpu", lambda: True)
    monkeypatch.syspath_prepend(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    from harness import spec
    found = spec.resolve(spec.load_benchmark(), cell)
    config, traffic = found["config"], found["traffic"]
    family = spec.load_module("families", config["family"]).build(
        config, traffic)
    comm = CommContext(mesh=_build_mesh(topo.devices[:1], 1), n_dcn=1,
                       n_ici=1)
    rep = comm.replicated_sharding()

    def shaped(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    tx = getattr(optax, traffic["optimizer"]["name"])(
        traffic["optimizer"]["learning_rate"])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(family.init_params, key)
    batch = jax.eval_shape(
        lambda k: family.make_batch(k, traffic["seqs_per_chip"]), key)
    compiled = make_dp_train_step(comm, family.loss_fn, tx).lower(
        shaped(params, rep), shaped(jax.eval_shape(tx.init, params), rep),
        shaped(batch, NamedSharding(comm.mesh, P(comm.dp_axes)))).compile()
    return compiled, config, traffic


def _used_gib(memory) -> float:
    return (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.generated_code_size_in_bytes) / 2 ** 30


def test_zaya_cell_step_fits_a_v5e(topo, monkeypatch):
    """``zaya1_8b.fused_1c``'s step at the published widths and 16 384
    positions: the compiler takes it (the long-form flash kernels at 8
    heads, the top-1 share's row kernels at 16 chunks of 1 024 rows, the
    gate kernel's column halves at an expert width of 2048), its memory
    stays under the chip's 15.75 GiB, and no ``[tokens, vocabulary]``
    logits array exists in it — the head's blocks of 1 024 rows do."""
    compiled, config, traffic = _compiled_cell_step(topo, monkeypatch,
                                                    "zaya1_8b.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 696,182,859 x 4 B = 7.78 GiB
    assert 7.7 < memory.argument_size_in_bytes / 2 ** 30 < 7.9
    assert _used_gib(memory) < 15.75
    text = compiled.as_text()
    tokens, vocab = traffic["seq_len"], config["vocab_size"]
    assert f"[{tokens},{vocab}]" not in text
    assert f"[1,{tokens},{vocab}]" not in text
    assert f"f32[1024,{vocab}]" in text              # a block of the head
    # a layer: flash forward, its recomputation, two backward kernels; the
    # spread (+ its recomputation), the scaled spread, the gate (+ its
    # recomputation) and its backward; twelve grouped matmuls; the
    # selection (+ its recomputation)
    assert text.count('custom_call_target="tpu_custom_call"') == 4 * 24
    assert len(_route_kernels(text)) == 4 * 2
    for scope in ("attn_cca/pallas_call", "bps.cca.mix", "bps.zaya.router",
                  "bps.head", "bps.moe.experts"):
        assert scope in text


def test_glm47_flash_cell_step_fits_a_v5e(topo, monkeypatch):
    """``glm47_flash.fused_1c``'s step at the published widths, 2 x 8 192
    positions: Mosaic takes the flash kernels at head size 256 in the long
    form (never lowered at that width before: four spans of 2 048 resident
    rows) in all six blocks and the top-4 share's row kernels over 65 536
    pair rows; the program's memory stays under the chip's 15.75 GiB; and
    neither head's ``[tokens, vocabulary]`` logits exist in it — the
    blocks of 8 192 rows do."""
    compiled, config, traffic = _compiled_cell_step(topo, monkeypatch,
                                                    "glm47_flash.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 706,518,848 x 4 B = 7.90 GiB
    assert 7.85 < memory.argument_size_in_bytes / 2 ** 30 < 7.95
    assert _used_gib(memory) < 15.75
    text = compiled.as_text()
    tokens = traffic["seq_len"] * traffic["seqs_per_chip"]
    vocab = config["vocab_size"]
    for whole in (f"[{tokens},{vocab}]",
                  f"[{traffic['seqs_per_chip']},{traffic['seq_len']},{vocab}]"):
        assert whole not in text
    assert f"f32[8192,{vocab}]" in text              # a block of a head
    # a block: flash forward, its recomputation, two backward kernels; a
    # sparse block besides: twelve grouped matmuls, the spread (+ its
    # recomputation), the scaled spread, the gate (+ its recomputation)
    # and its backward, the selection (+ its recomputation)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        6 * 4 + 5 * 20)
    assert len(_route_kernels(text)) == 5 * 2
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum(c.endswith("/attn_mla/pallas_call") for c in calls) == 24
    assert sum("/mtp/" in c for c in calls) == 22 + 2
    assert sum("bps.moe.experts" in c for c in calls) == 60
    for scope in ("bps.mla.latent", "bps.moe.score", "bps.moe.shared",
                  "bps.head", "/mtp/"):
        assert scope in text


def test_olmoe_cell_step_holds_no_whole_logits(topo, monkeypatch):
    """``olmoe_1b_7b.fused_1c``'s step at the published widths, 4 x 4 096
    positions: the untied head goes through ``blocked_token_nll`` with
    ``lm_head``'s kernel read as it lies, [h, V].  No ``[tokens,
    vocabulary]`` logits exist in the program — four blocks of 4 096 rows
    do — which is 1.8 GiB of the 14.73 the step took on whole logits; and
    nothing copies or transposes a kernel-sized array: handing the head
    ``kernel.T`` costs six such copies a step (the kernel, ``mu`` and
    ``nu`` to [V, h] and back, 4.9 GB moved) that one fusion each would
    otherwise read in place."""
    compiled, config, traffic = _compiled_cell_step(topo, monkeypatch,
                                                    "olmoe_1b_7b.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 625,674,240 x 4 B = 6.99 GiB
    assert 6.95 < memory.argument_size_in_bytes / 2 ** 30 < 7.05
    assert _used_gib(memory) < 13.2
    text = compiled.as_text()
    seqs, seq_len = traffic["seqs_per_chip"], traffic["seq_len"]
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    for whole in (f"[{seqs * seq_len},{vocab}]", f"[{seqs},{seq_len},{vocab}]"):
        assert whole not in text
    assert f"f32[4096,{vocab}]" in text              # a block of the head
    assert "bps.head" in text
    # flash forward and backward, nine grouped matmuls, the selection
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 9 + 1
    assert len(_route_kernels(text)) == 1
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= \w+\[({hidden},{vocab}|{vocab},{hidden})\]"
                          r"\S* (copy|transpose)\(", line)]
    assert not moved, moved


def test_nemotron3_super_cell_step_fits_a_v5e(topo, monkeypatch):
    """``nemotron3_super.fused_1c``'s step at the published widths, rung
    (b) of ISSUE 39's memory ladder (1 x 8 192 positions with the module):
    Mosaic takes the state-space scan's kernels (``bps_ssd_fwd`` twice a
    block — the forward and, under ``remat``, the one that stores the
    chunk-start states — and ``bps_ssd_bwd`` once), the ungated experts'
    grouped matmuls and the ``relu2`` row kernel (thirds of its 2 688
    columns) at windows of 6 144 of the 180 224 pair rows, inside loops
    with a runtime trip count; the program's memory stays under the
    chip's 15.75 GiB (12.05 by ``memory_analysis`` since the layer works
    in windows, PR 40; 15.09 before: an ``E`` block's backward held
    ``[180224, 2688]`` arrays of 0.90 GiB); neither head's ``[tokens,
    vocabulary]`` logits exist outside a block; and no decay matrix ``L``
    ([.., 128, 128] float32 a chunk and head) exists outside a kernel."""
    compiled, config, traffic = _compiled_cell_step(
        topo, monkeypatch, "nemotron3_super.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 838,249,968 x 4 B = 9.37 GiB
    assert 9.3 < memory.argument_size_in_bytes / 2 ** 30 < 9.45
    assert _used_gib(memory) < 12.4
    text = compiled.as_text()
    tokens = traffic["seq_len"] * traffic["seqs_per_chip"]
    vocab = config["vocab_size"]
    # one block of the head IS all 8 192 rows here (0.5 GiB of float32
    # logits: ``logit_block_rows``); two sequences' would not be
    assert f"[{2 * tokens},{vocab}]" not in text
    assert "bps.head" in text
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum(c.endswith("bps_ssd_fwd/pallas_call") for c in calls) == 10
    assert sum(c.endswith("bps_ssd_bwd/pallas_call") for c in calls) == 5
    assert all("bps.ssm.scan" in c for c in calls if "bps_ssd" in c)
    # per * block: flash forward, its recomputation, two backward kernels
    assert sum(c.endswith("/attn/pallas_call") for c in calls) == 8
    # per E block, each in a loop over the windows: two grouped matmuls
    # forward, two recomputed under ``remat``, and the layer's backward —
    # its own forward again (two) and four gradients; the activation three
    # times and its backward; the spread three times and the scaled spread;
    # the token-order sum three times (the combine's forward and recomputed,
    # the dispatch's backward: PR 49); and OUTSIDE the loops the selection
    # and its recomputation, which ``route_select_ms`` reads (twelve a step).
    # ``latent_moe_ms`` reads the ten by this rule, ``jvp(...)`` or not
    experts = [c for c in calls
               if re.search(r"bps\.moe\.experts/.*pallas_call$", c)]
    assert len(experts) == 6 * 10
    assert sum("bps.moe.experts" in c for c in calls) == 6 * 10
    assert sum(c.endswith("bps_moe_act/pallas_call") for c in calls) == 18
    assert sum(c.endswith("bps_moe_act_bwd/pallas_call") for c in calls) == 6
    assert sum(c.endswith("bps_moe_spread/pallas_call") for c in calls) == 18
    assert not any("bps_moe_gate" in c for c in calls)     # no gate
    select = _route_kernels(text)
    assert len(select) == 6 * 2
    assert sum(c.endswith("bps_moe_sum/pallas_call") for c in calls) == 18
    assert len(calls) == 15 + 8 + 6 * (10 + 4 + 4 + 3 + 2)
    moe = [c for c in calls if "bps.moe." in c and c not in select]
    assert all("/while/body/" in c for c in moe)
    assert not any("/while/body/" in c for c in select)
    # the module's block: 4 flash calls, its experts' twenty-one kernels
    # (three of them the token-order sum), its selection twice
    assert sum(bool(re.search(r"/mtp/.*pallas_call$", c))
               for c in calls) == 4 + 21 + 2
    # no array of all 180 224 pair rows but the sort's columns
    assert not re.search(r"\[180224,\d+\]", text)
    for scope in ("bps.ssm.in_proj", "bps.ssm.conv", "bps.ssm.gate_norm",
                  "bps.ssm.out_proj", "bps.moe.latent_down",
                  "bps.moe.latent_up", "bps.moe.shared", "bps.moe.score",
                  "/mtp/", "/mixer_ssm/"):
        assert scope in text
    # the decay matrix of a chunk lives in VMEM only
    chunks = traffic["seq_len"] // config["chunk_size"]
    assert not re.search(rf"f32\[[\d,]*{chunks},\d+,128,128\]", text)
    assert not re.search(r"f32\[[\d,]*,128,128\]\S* (fusion|exponential)\(",
                         text)


def test_ling3_flash_cell_step_fits_a_v5e(topo, monkeypatch):
    """``ling3_flash.fused_1c``'s step at the published widths on the rung
    of ISSUE 43's memory ladder the cell takes — (a), 2 x 8 192 positions,
    the first whose whole step compiles under 15.0 GiB (14.74 by
    ``memory_analysis``: arguments 8.49 + temp 6.18 + code 0.07) and runs
    on the chip: Mosaic takes the delta-rule
    scan's kernels at 32 heads of 128 x 128 in chunks of 128 (``bps_kda_fwd``
    twice a KDA layer — the forward and, under ``remat``, the one that
    stores the chunk-start states — and ``bps_kda_bwd`` once, whose body is
    ``jax.vjp`` of the chunk's text), the row kernels around the scan
    (``ops/kda_rows.py``: no float32 ``[2, 8192, 12288]`` array outside a
    kernel), the flash kernels with q.k at 256
    lanes and v at 128, and the GATED experts in windows behind the group
    limit; arguments + temp + code stay under 15.0 GiB; the head's
    ``[tokens, 19648]`` logits exist only a block at a time; and no
    ``[.., C, C, 128]`` float32 array (the channel-wise decay of a chunk's
    score pairs, C = 64 or 128) exists outside a kernel."""
    compiled, config, traffic = _compiled_cell_step(
        topo, monkeypatch, "ling3_flash.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 759,799,584 x 4 B = 8.49 GiB
    assert 8.45 < memory.argument_size_in_bytes / 2 ** 30 < 8.55
    assert _used_gib(memory) < 15.0
    assert traffic["seqs_per_chip"] == 2                   # rung (a)
    text = compiled.as_text()
    tokens = traffic["seq_len"] * traffic["seqs_per_chip"]
    vocab = config["vocab_size"]
    if tokens * vocab * 4 > 2 ** 30:     # else one block IS all the rows
        assert f"[{tokens},{vocab}]" not in text
    assert "bps.head" in text
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kda = [c for c in calls
           if re.search(r"bps\.kda\.scan\)*/.*pallas_call$", c)]
    assert sum(c.endswith("bps_kda_fwd/pallas_call") for c in kda) == 10
    assert sum(c.endswith("bps_kda_bwd/pallas_call") for c in kda) == 5
    assert len(kda) == 15
    # ... with the chunk text of PR 45: tracing the step left the gauge
    # ``kda_matmul_operand_KiB`` reads at what Mosaic is handed a chunk and
    # head (a sub-block's own stacked rows in the score products: 5 632 KiB
    # where the text before read 6 336)
    import byteps_tpu as bps
    assert bps.metrics_snapshot()["gauges"][
        "kda.matmul_operand_bytes_per_chunk"] == 5632 * 1024
    # the row stages around the scan (ops/kda_rows.py), what
    # ``kda_rows_ms`` reads by this rule: a KDA layer's two kernels in
    # front of the scan and behind it, forward, recomputed and backward
    rows = [c for c in calls
            if re.search(r"bps\.kda\.(pre|out)\)*/.*pallas_call$", c)]
    for name, count in (("bps_kda_pre_fwd", 10), ("bps_kda_pre_bwd", 5),
                        ("bps_kda_post_fwd", 10), ("bps_kda_post_bwd", 5)):
        assert sum(c.endswith(f"{name}/pallas_call") for c in rows) == count
    assert len(rows) == 30
    assert len(kda) + len(rows) == sum("bps_kda" in c for c in calls)
    # no float32 copy of the fused q | k | v rows exists outside a kernel
    # (the parent held ~8 of 805 MB a layer), nor the three by head
    assert "f32[2,8192,12288]" not in text
    assert "f32[2,8192,3,32,128]" not in text
    # the one MLA layer: flash forward, its recomputation, two backward
    # kernels (T = 8192 is past the resident form)
    assert sum(c.endswith("/attn_mla/pallas_call") for c in calls) == 4
    # four sparse layers: the selection and its recomputation
    assert len(_route_kernels(text)) == 4 * 2
    experts = [c for c in calls
               if re.search(r"bps\.moe\.experts/.*pallas_call$", c)]
    # per sparse layer, in the loops over the windows: three grouped
    # matmuls forward, three recomputed under ``remat``, six gradients —
    # what ``moe_ms`` reads by this rule
    assert len(experts) == 4 * 12
    assert all("/while/body/" in c for c in experts)
    assert any("bps_moe_gate" in c for c in calls)         # gated experts
    assert not re.search(r"f32\[[\d,]*(64,64|128,128),128\]", text)
    for scope in ("bps.kda.proj", "bps.kda.pre", "bps.kda.scan",
                  "bps.kda.out", "bps.mla.latent", "bps.mla.gate",
                  "bps.moe.group_limit", "bps.moe.score", "bps.moe.shared",
                  "mixer_kda", "attn_mla"):
        assert scope in text, scope


@pytest.mark.parametrize("seqs,heads", [(4, (16, 32)), (1, (4, 4))],
                         ids=["qwen3_next_cell", "equal_heads"])
@pytest.mark.parametrize("chunk", [64, 128])
def test_head_decay_scan_kernels_compile_for_a_v5e(one_chip, seqs, heads,
                                                   chunk):
    """``ops/gdn_scan.py`` at ``qwen3_next_80b.fused_1c``'s shape — 4 x
    8 192 positions, 16 key heads under 32 value heads of 128 — and with a
    value head a key head (two key heads a grid step), forward (storing the
    chunk-start states) and backward (``jax.vjp`` of the chunk's text in
    the kernel): Mosaic takes the [C, 1] - [1, C] difference, the masked
    ``exp``, the [1, C] rows of ``G`` and their cotangent."""
    import importlib
    gdn = importlib.import_module("byteps_tpu.ops.gdn_scan")
    hk, hv = heads

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = shaped((seqs, 8192, hk, 128)), shaped((seqs, 8192, hv, 128))
    g = shaped((seqs, 8192, hv), jnp.float32)

    def objective(q, k, v, g, beta):
        return jnp.sum(gdn.gdn_scan(q, k, v, g, beta, chunk=chunk,
                                    interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(objective, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, v, g, g).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("bps_gdn_fwd" in c for c in calls) == 1
    assert sum("bps_gdn_bwd" in c for c in calls) == 1
    # q and k go in at the KEY heads' width and the gate as one number a
    # position and value head: no repeated q / k, no [T, H, 128] gate
    assert f"bf16[{seqs},8192,{hv * 128}]" in text
    assert f"f32[{seqs},8192,{hv},128]" not in text
    assert f"f32[{seqs},8192,{hv * 128}]" not in text


def test_qwen3_next_80b_cell_step_fits_a_v5e(topo, monkeypatch):
    """``qwen3_next_80b.fused_1c``'s step at the published widths on the
    rung of ISSUE 46's memory ladder the cell takes: Mosaic takes the
    head-decay scan's kernels under ``bps.gdn.scan`` in all three DeltaNet
    layers (``bps_gdn_fwd`` twice a layer — the forward and, under
    ``remat``, the one that stores the chunk-start states — and
    ``bps_gdn_bwd`` once), the row kernels around it — the input stage as
    one pass over ``in_proj_qkvz``'s result under ``bps.gdn.pre`` (q | k |
    v of 2048 | 2048 | 4096 lanes, no decay slice: no float32 ``[4, 8192,
    8192]`` array and no slice copy of those columns outside a kernel), the
    output stage with the SiLU gate under ``bps.gdn.out`` —, the flash
    kernels at 16 heads of 256 under
    ``attn`` and the selection kernel under ``bps.moe.route`` in all four
    sparse MLPs, whose held experts (32 of 512 at top-10: a 16th of 327 680
    pair rows live) run in windows of 40 960 rows under ``bps.moe.window``
    (PR 49: ``layer_plan``'s crossover at an eighth) — twelve grouped
    matmuls a layer as before (three forward, three inside the backward
    loop's ``jax.vjp``, six backward: the ``remat`` forward of a windowed
    layer has no consumer and goes) and no ``[327 680, 2048]`` array; the
    head's ``[tokens, 18992]`` logits exist only a block at a time; q and
    k of the scan are never repeated to the value heads and the gate is
    never broadcast over a head's channels."""
    compiled, config, traffic = _compiled_cell_step(
        topo, monkeypatch, "qwen3_next_80b.fused_1c")
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 625,667,136 x 4 B = 6.99 GiB
    assert 6.95 < memory.argument_size_in_bytes / 2 ** 30 < 7.05
    # rung (a), 4 x 8 192 positions: arguments 6.99 + temp 6.67 + code
    # 0.21 = 13.87 GiB at the scan's chunk of 128 with the expert layers in
    # windows (PR 49; on whole [327 680, 2048] pair-row arrays, where the
    # peak was the attention layer's sparse MLP in the backward pass:
    # temp 7.17 + code 0.07 = 14.24)
    assert traffic["seqs_per_chip"] == 4
    assert _used_gib(memory) < 15.0
    seqs = traffic["seqs_per_chip"]
    text = compiled.as_text()
    tokens = traffic["seq_len"] * seqs
    assert f"[{tokens},{config['vocab_size']}]" not in text
    assert "bps.head" in text
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    scan = [c for c in calls
            if re.search(r"bps\.gdn\.scan\)*/.*pallas_call$", c)]
    assert sum(c.endswith("bps_gdn_fwd/pallas_call") for c in scan) == 6
    assert sum(c.endswith("bps_gdn_bwd/pallas_call") for c in scan) == 3
    assert len(scan) == 9 == sum("bps_gdn" in c for c in calls)
    rows = [c for c in calls
            if re.search(r"bps\.gdn\.(pre|out)\)*/.*pallas_call$", c)]
    # what ``gdn_rows_ms`` reads by this rule: a layer's two kernels in
    # front of the scan and behind it, forward, recomputed and backward
    for name, count in (("bps_kda_pre_fwd", 6), ("bps_kda_pre_bwd", 3),
                        ("bps_kda_post_fwd", 6), ("bps_kda_post_bwd", 3)):
        assert sum(c.endswith(f"{name}/pallas_call") for c in rows) == count
    assert len(rows) == 18
    # no float32 copy of the q | k | v columns exists outside a kernel (the
    # parent held ~8 of 1 GiB a layer), and nothing slices them out of the
    # projection: q | k | v leave the kernel as the scan reads them
    assert f"f32[{seqs},8192,8192]" not in text
    assert f"bf16[{seqs},8192,8192]" not in text
    # the one attention layer: flash forward, its recomputation, two
    # backward kernels (T = 8192 at 256 lanes is past the resident form)
    assert sum(c.endswith("/attn/pallas_call") for c in calls) == 4
    # four sparse layers: the selection and its recomputation
    assert len(_route_kernels(text)) == 4 * 2
    experts = [c for c in calls
               if re.search(r"bps\.moe\.experts/.*pallas_call$", c)]
    assert len(experts) == 4 * 12
    # in windows: no array of all 327 680 pair rows is left
    assert "bps.moe.window" in text
    assert f"bf16[{tokens * config['num_experts_per_tok']},2048]" not in text
    # the scan reads q, k at 16 heads x 128 lanes; nothing repeats them to
    # 32 heads in float32 or lays the gate out a channel
    assert f"f32[{seqs},8192,32,128]" not in text
    for scope in ("bps.gdn.proj", "bps.gdn.pre", "bps.gdn.scan",
                  "bps.gdn.out", "bps.attn.gate", "bps.moe.route",
                  "bps.moe.shared", "mixer_gdn", "attn"):
        assert scope in text, scope


# ------------------------------------------------- EVA attention (PR 50)

EVABYTE_CELL = "evabyte_6b5.fused_1c"


@pytest.mark.parametrize("summary_block_k", [512, 128])
def test_eva_attention_compiles_for_a_v5e(one_chip, summary_block_k):
    """``ops/eva_attention.py`` forward and backward at the cell's ``(1, T,
    32, 128)``, T = 16 384: Mosaic takes the flash kernels under the
    staircase mask (a scalar division of the runtime row offset, one more
    compare a score) beside the own-window causal calls at T = 2 048 in a
    batch of 256 — five kernels: each set's forward, the windows'
    one-kernel backward, the summaries' two-kernel one — and no ``[T,
    T / 16]`` or ``[T, 2048]`` score array exists outside them.
    ``summary_block_k`` is the tests' hook: 512 is what runs, 128 (a
    step's width, the narrower sub-block ISSUE 50 asked to try) compiles
    too."""
    import importlib
    eva = importlib.import_module("byteps_tpu.ops.eva_attention")
    t = 16384
    x = jax.ShapeDtypeStruct((1, t, 32, 128), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((32, 128), jnp.float32, sharding=one_chip)

    def objective(q, k, v, mu, phi):
        return jnp.sum(eva.eva_attention(
            q, k, v, mu, phi, window=2048, chunk=16,
            summary_block_k=summary_block_k, interpret=False
        ).astype(jnp.float32))

    text = jax.jit(jax.grad(objective, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, w, w).compile().as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("bps.eva.local" in c for c in calls) == 2
    assert sum("bps.eva.summary" in c for c in calls) == 3
    assert len(calls) == 5
    for scores in (f"[32,{t},{t // 16}]", f"[32,{t},2048]",
                   f"[256,2048,2048]", f"[1,32,{t},{t // 16}]"):
        assert scores not in text


def test_evabyte_cell_step_fits_a_v5e(topo, monkeypatch):
    """``evabyte_6b5.fused_1c``'s step at the published widths on the rung
    of ISSUE 50's memory ladder the cell takes (16 384 positions): under
    15.0 GiB; seven flash kernels a layer under ``attn`` — the own-window
    set and the summary set forward, both again under ``remat``, the
    windows' one-kernel backward and the summaries' two-kernel one; the
    block inputs a ``remat`` keeps are float32; no score array of either
    key set and no ``[tokens, 8 x 320]`` logits whole in float32 beside
    their blocks."""
    compiled, config, traffic = _compiled_cell_step(topo, monkeypatch,
                                                    EVABYTE_CELL)
    memory = compiled.memory_analysis()
    # weights and two moments: 3 x 821,366,784 x 4 B = 9.18 GiB
    assert 9.15 < memory.argument_size_in_bytes / 2 ** 30 < 9.21
    # rung (a): arguments 9.18 + temp 4.70 + code 0.03 = 13.91 GiB
    assert traffic["seq_len"] == 16384 and traffic["seqs_per_chip"] == 1
    assert _used_gib(memory) < 15.0
    text = compiled.as_text()
    t = traffic["seq_len"]
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4 * 7
    for layer in range(4):
        here = [c for c in calls if f"/h{layer}/attn/" in c]
        assert sum(c.endswith("bps.eva.local/pallas_call")
                   for c in here) == 3
        assert sum(c.endswith("bps.eva.summary/pallas_call")
                   for c in here) == 4
        assert sum("rematted_computation" in c for c in here) == 2
    for scores in (f"[32,{t},{t // 16}]", f"[32,{t},2048]",
                   f"[{t // 64},2048,2048]"):
        assert scores not in text
    for scope in ("bps.eva.pool", "bps.eva.merge", "bps.eva.rope",
                  "bps.head"):
        assert scope in text
    assert f"f32[1,{t},4096]" in text                # the residual stream


def test_evabyte_reference_fits_beside_the_harness_s_state(topo, monkeypatch):
    """The float32 reference of ``evabyte_6b5.fused_1c`` is what memory
    decides: ``value_and_grad(reference_loss)`` runs beside the harness's
    parameters, their gradient and two moments (4 x 3.06 = 12.24 GiB), so
    its temp + code stay under 15.0 - 12.24 GiB at the rung taken (one head
    at a time from its projections to its ``W_o`` product: 2.06 GiB at
    16 384 positions; all heads' q, k, v side by side read 3.77 at
    8 192)."""
    import os
    monkeypatch.syspath_prepend(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    from harness import spec
    found = spec.resolve(spec.load_benchmark(), EVABYTE_CELL)
    config, traffic = found["config"], found["traffic"]
    family = spec.load_module("families", config["family"]).build(
        config, traffic)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(family.init_params, key)
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1), key)
    with jax.default_matmul_precision("highest"):
        memory = jax.jit(jax.value_and_grad(family.reference_loss)).lower(
            shaped(params), shaped(batch)).compile().memory_analysis()
    state = 4 * memory.argument_size_in_bytes / 2 ** 30
    assert 12.2 < state < 12.3
    assert state + (memory.temp_size_in_bytes
                    + memory.generated_code_size_in_bytes) / 2 ** 30 < 15.0
