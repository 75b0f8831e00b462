"""The arithmetic of the readers and operation counts that came with the
``mellum2-12b-a2.5b-ep4-l4`` configuration, on synthetic stamps; the
driver's by-name comparison of the first step; the driver refusing a
checkout whose ``FAMILIES`` lacks the family before it starts anything;
and the configuration file against the catalog's published numbers."""

import json
import os
import types

import pytest

from benchmark import flops_mellum
from benchmark.drivers import model_train_cycles_trunk as driver
from benchmark.harness import RunFailed
from benchmark.tests.test_metrics import cycle, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", "mellum2-12b-a2.5b-ep4-l4.json")))
M = CONFIG["model"]["config"]
TOKENS, SEQ = 2 * 8192, 8192
NEW = ["window_flash_roofline", "full_flash_roofline", "window_flash_share_of_step",
       "moe_gmm_roofline_softmax", "train_mfu_share_swa"]


def ctx(counters, cycles=None):
    return types.SimpleNamespace(
        stamps=dict(cycles=cycles or [cycle(100.0, 4.0, 0.0)], t_open=100.0, steps_per_cycle=10,
                    tokens_per_step=TOKENS, saves=False, counters=counters),
        trace=None, peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
        config=CONFIG, traffic=dict(params=dict(seq=SEQ, batch=2)),
        run=types.SimpleNamespace(chips=1), device=dict(memory_peak_bytes=12 * 2 ** 30))


def even_counters(steps=10):
    """An even router: 8 of 64 over 16 held experts, 4 layers."""
    here = steps * 4 * TOKENS * 8 * 16 // 64
    return {"moe.assignments_here": here, "moe.assignments_absent": steps * 4 * TOKENS * 8 - here,
            "moe.load_max_over_mean": 1.25 * steps * 4, "moe.layer_steps": steps * 4,
            "moe.dropped": 0, "train.steps_counted": steps}


def traced(events, steps=20, step_s=0.4):
    return types.SimpleNamespace(
        used_planes=lambda: ["/device:TPU:0"], op_seconds=lambda: events,
        main_module=lambda: ("jit_step_fn", [step_s] * steps))


def test_mellum_operations_from_shapes():
    """The issue's arithmetic: 21,233,664 in a layer's attention; a window
    layer's scores over 7.86M pairs a head against a full layer's 33.55M
    (23.4%); 6 x 33.8M operations a token a layer in matrix products with 2
    of 8 assignments landing here, 6 x 56.6M in the head."""
    assert flops_mellum.attention_projection_params(M) == 21_233_664
    assert flops_mellum.layer_types(M) == ["sliding_attention"] * 3 + ["full_attention"]
    window, full = (flops_mellum.pairs_per_head(M, kind, SEQ) for kind in ("sliding_attention", "full_attention"))
    assert window == 1024 * 1025 // 2 + 7168 * 1024 == 7_864_832 and full == 8192 * 8193 // 2
    assert window / full == pytest.approx(0.234, abs=5e-4)
    assert flops_mellum.pairs_per_head(M, "sliding_attention", 512) == 512 * 513 // 2  # a window past T
    assert flops_mellum.expert_mlp_flops(M) == 3 * 2 * 2304 * 896
    per_layer = 2 * 21_233_664 + 2 * 2304 * 64 + 2 * flops_mellum.expert_mlp_flops(M)
    assert per_layer == pytest.approx(2 * 33.8e6, rel=3e-3)
    scores = 4 * 128 * 32 * (3 * window + full) / SEQ
    forward = flops_mellum.forward_flops_per_token(M, SEQ, 2.0)
    assert forward == pytest.approx(4 * per_layer + scores + 2 * 2304 * 24576)
    assert flops_mellum.train_flops_per_token(M, SEQ, 2.0) == 3 * forward
    # a step: 18.8e12 in matrix products, 5.6e12 in scores (3 x the forward's; the kernels'
    # rooflines count the flash backward's recomputed scores too: 3.5 x, the issue's 6.55e12)
    assert 3 * TOKENS * (forward - scores) == pytest.approx(18.8e12, rel=5e-3)
    assert 3.5 * TOKENS * scores == pytest.approx(6.55e12, rel=5e-3)
    assert flops_mellum.flash_flops(M, "full_attention", 2, SEQ) == 7 * 2 * 2 * 32 * full * 128
    assert flops_mellum.flash_flops(M, "sliding_attention", 2, SEQ) == pytest.approx(0.90e12, rel=5e-3)
    assert flops_mellum.flash_bytes(M, 2, SEQ) == 12 * 2 * 32 * SEQ * 128 * 2
    assert flops_mellum.moe_gmm_flops(M, 1000) == 3 * 1000 * 3 * 2 * 2304 * 896
    assert flops_mellum.moe_gmm_bytes(M) == 3 * 4 * 16 * 3 * 2304 * 896 * 2


def test_mellum_counter_readers():
    c = ctx(even_counters())
    assert reader("layer_metrics", "moe_load_max_over_mean")(c) == pytest.approx(1.25)
    # 16,384 tokens x 8 / 64 = 2,048 a held expert a step: 1/4 of the deployment's 8,192
    assert reader("layer_metrics", "moe_assignments_per_expert")(c) == pytest.approx(2048.0)
    mfu = reader("layer_metrics", "train_mfu_share_swa")(c)
    per_token = flops_mellum.train_flops_per_token(M, SEQ, 2.0)
    assert mfu == pytest.approx(100 * per_token * (10 * TOKENS / 4.0) / 197e12, rel=1e-9)
    assert 0 < mfu < 100


def test_flash_rooflines_read_each_kind_of_layers_kernels():
    """20 traced steps: the nine window kernels took 36 ms a step, the full
    layer's three 44 ms; a step 0.4 s. A kernel under another scope, and an
    operation that is no kernel under this one, are not counted."""
    call = 'custom-call(...), custom_call_target="tpu_custom_call"'
    events = {f"%swa.attend_window.3 = bf16[64,8192,128]{{2,1,0}} {call}": (20 * 0.012,),
              f"%swa.attend_window.7 = (bf16[64,8192,128]{{2,1,0}}, bf16[64,8192,128]{{2,1,0}}) {call}": (20 * 0.024,),
              f"%swa.attend_full.2 = bf16[64,8192,128]{{2,1,0}} {call}": (20 * 0.044,),
              f"%mla.attend.2 = bf16[64,8192,128]{{2,1,0}} {call}": (20 * 0.5,),
              "%fusion.9 = bf16[2,8192,32,128]{3,2,1,0} fusion(%swa.attend_window.3)": (20 * 0.3,)}
    c = ctx(even_counters())
    c.trace = traced(events)
    least = {kind: flops_mellum.layers_of(M, kind) * max(
        flops_mellum.flash_flops(M, kind, 2, SEQ) / 197e12, flops_mellum.flash_bytes(M, 2, SEQ) / 819e9)
        for kind in ("sliding_attention", "full_attention")}
    assert least["sliding_attention"] == pytest.approx(3 * 0.90e12 / 197e12, rel=5e-3)  # bound by operations
    assert reader("layer_metrics", "window_flash_roofline")(c) == pytest.approx(
        100 * least["sliding_attention"] / 0.036)
    assert reader("layer_metrics", "full_flash_roofline")(c) == pytest.approx(
        100 * least["full_attention"] / 0.044)
    assert reader("layer_metrics", "window_flash_share_of_step")(c) == pytest.approx(100 * 0.036 / 0.4)
    for name in NEW[:3]:
        assert 0 < reader("layer_metrics", name)(c) < 100


def test_softmax_gmm_roofline_sets_the_traced_steps_work_against_their_time():
    c = ctx({**even_counters(50), "moe.assignments_here": 3 * even_counters(50)["moe.assignments_here"]})
    c.stamps["counters_traced"] = even_counters(20)
    events = {"%gmm.7 = bf16[131072,896]{1,0} custom-call(...)": (20 * 0.040,),
              "%tgmm.3 = (bf16[16,2304,896]{2,1,0}) custom-call(...)": (20 * 0.020,),
              "%tgmm.9 = bf16[128,128,2304]{2,1,0} custom-call(...)": (20 * 0.050,)}  # row collecting
    c.trace = traced(events)
    per_step = 4 * TOKENS * 8 * 16 // 64
    least = max(flops_mellum.moe_gmm_flops(M, per_step) / 197e12, flops_mellum.moe_gmm_bytes(M) / 819e9)
    assert reader("layer_metrics", "moe_gmm_roofline_softmax")(c) == pytest.approx(100 * least / 0.060)


@pytest.mark.parametrize("name", NEW)
def test_mellum_readers_with_nothing_to_read_are_none(name):
    """Another driver's stamps (no counters), no trace, or a program whose
    trace holds no such kernel (the parent's): the metric is left out."""
    bare = ctx(None)
    bare.stamps.pop("counters")
    assert reader("layer_metrics", name)(bare) is None
    other = ctx({})
    other.config = dict(gpt_config={})
    assert reader("layer_metrics", name)(other) is None
    if name != "train_mfu_share_swa":
        no_kernel = ctx(even_counters())
        no_kernel.stamps["counters_traced"] = even_counters(20)
        no_kernel.trace = traced({"%fusion.1 = bf16[2,8192,2304]{2,1,0} fusion(...)": (1.0,)})
        assert reader("layer_metrics", name)(no_kernel) is None


def test_first_step_is_held_to_each_limit_by_name():
    want = dict(trunk_loss=10.5, grad_norm=2.0, assignments_here_by_layer=[32000, 33000])
    expected = dict(first_step=dict(values={"b2x8192": want}, tolerances=dict(
        trunk_loss=1e-3, grad_norm_rel=1e-2, assignments_here_by_layer_rel=1e-2)))
    run = types.SimpleNamespace(config=dict(expected=expected), traffic=dict(params=dict(seq=SEQ)))

    def verdict(drop=(), **moved):
        got = {"train.trunk_loss": 10.5, "grad_norm": 2.0,
               "moe.assignments_here_by_layer": [32000, 33000], "moe.dropped": 0, **moved}
        for key in drop:
            del got[key]
        checks = {}
        driver.check_first_step(run, dict(start_step=0, tokens_per_step=TOKENS, first_step=got), checks)
        return {k for k, v in checks.items() if v is False}

    assert verdict() == set()
    assert verdict(**{"train.trunk_loss": 10.502}) == {"trunk_loss_ok"}
    assert verdict(grad_norm=2.03) == {"grad_norm_ok"}
    assert verdict(**{"moe.assignments_here_by_layer": [32000, 33400]}) == {"assignments_here_by_layer_ok"}
    assert verdict(**{"moe.assignments_here_by_layer": [32000]}) == {"assignments_here_by_layer_ok"}
    assert verdict(drop=["train.trunk_loss"]) == {"trunk_loss_ok"}  # a name the step did not return
    other_shape = {}
    driver.check_first_step(run, dict(start_step=0, tokens_per_step=4 * SEQ, first_step={}), other_shape)
    assert other_shape["first_step_ok"] is False
    resumed = {}
    driver.check_first_step(run, dict(start_step=30, tokens_per_step=TOKENS, first_step={}), resumed)
    assert resumed == {}


def test_a_checkout_without_the_family_is_refused_before_anything_starts(tmp_path, monkeypatch):
    """The parent's ``FAMILIES`` has no ``mellum``: the driver raises at
    once, with a message, and ``launch`` is never reached."""
    build = tmp_path / "dlrover_tpu" / "models"
    build.mkdir(parents=True)
    (build / "build.py").write_text('FAMILIES = {\n    "gpt": ("gpt", "GPT", "GPTConfig"),\n}\n')
    assert driver.families_of(str(tmp_path)) == ["gpt"]
    assert driver.families_of(str(tmp_path / "nowhere")) == []
    assert "mellum" in driver.families_of(ROOT)
    monkeypatch.setattr(driver.harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(driver, "launch", lambda *a, **k: pytest.fail("a process was started"))
    with pytest.raises(RunFailed, match="FAMILIES lacks the family 'mellum'"):
        driver.run(types.SimpleNamespace(config=CONFIG))


def test_mellum_configuration_keeps_the_published_widths():
    """Every number of the catalog's entry is in the file under its key;
    only the keys in ``reduced`` differ; the model as run has them too."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(catalog) if '"Mellum2-12B-A2.5B-Instruct"' in l)
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
        if key in M and key not in CONFIG["reduced"] + ["layer_types", "mlp_layer_types"]:
            assert M[key] == value, key
    assert M["layer_types"] == row["config"]["layer_types"][:4]
    assert M["num_experts"] == 64 and M["experts_held"] == CONFIG["num_experts"] == 16
    assert M["vocab_size"] == CONFIG["vocab_size"] == 98304 // 4
    assert M["num_hidden_layers"] == CONFIG["num_hidden_layers"] == 4
    assert set(CONFIG["expected"]["first_step"]["tolerances"]) == {
        "trunk_loss", "grad_norm_rel", "assignments_here_by_layer_rel"}


def test_mellum_limits_admit_the_system_and_refuse_each_control():
    """The configuration's own readings (my chip runs, PR 47) against its
    limits, through the driver's comparison: the timed path passes every
    limit and each of the three controls the issue names fails at least
    one: 8-bit matrices the per-layer counts (under the objective as it
    stands), the window ignored and YaRN off the loss and the counts (YaRN
    off the counts in the fourth layer alone); those two's gradient norms
    were read with the gates still trained and are held against that
    reading's own reference."""
    first = CONFIG["expected"]["first_step"]
    want, tol, readings = first["values"]["b2x8192"], first["tolerances"], first["readings"]
    assert CONFIG["expected"]["train_canary_loss"]["values"]["b2x8192"] == want["trunk_loss"]
    assert CONFIG["expected"]["train_canary_loss"]["tolerance"] == tol["trunk_loss"]

    def failed(reading):
        return {name for name in want if name in reading
                and not driver.within(reading[name], want[name], tol, name)}

    system = readings["system_on_v5e"]
    assert failed(system) == set() and set(want) <= set(system)
    assert failed(readings["reference_8bit_matrices"]) == {"assignments_here_by_layer"}
    for control in ("reference_window_ignored", "reference_yarn_off"):
        assert failed(readings[control]) == {"trunk_loss", "assignments_here_by_layer"}, control
        assert readings[control]["grad_norm_rel_off_gates_trained"] > 10 * tol["grad_norm_rel"]
    # room on both sides: the system's gap under a quarter of each limit; the nearest control a limit
    # refuses over twice it (the counts: 1.9 times, YaRN off's fourth layer)
    refused = [readings[c] for c in ("reference_window_ignored", "reference_yarn_off")]
    assert system["trunk_loss_off"] * 4 < tol["trunk_loss"] < min(r["trunk_loss_off"] for r in refused) / 2
    assert system["grad_norm_rel_off"] * 4 < tol["grad_norm_rel"] < min(
        r["grad_norm_rel_off_gates_trained"] for r in refused) / 2
    assert readings["reference_8bit_matrices"]["grad_norm_rel_off"] < tol["grad_norm_rel"]
    eight = readings["reference_8bit_matrices"]["assignments_rel_off_by_layer"]
    assert max(system["assignments_rel_off_by_layer"]) * 2.5 < tol["assignments_here_by_layer_rel"] < min(eight) / 2.5
    assert tol["assignments_here_by_layer_rel"] < max(readings["reference_yarn_off"]["assignments_rel_off_by_layer"]) / 1.8
    assert max(eight) > 4 * tol["assignments_here_by_layer_rel"]
