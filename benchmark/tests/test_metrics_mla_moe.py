"""The arithmetic of the readers and operation counts that came with the
``joyai-llm-flash-ep16`` configuration, on synthetic stamps, and the
configuration file against the catalog's published numbers."""

import json
import os
import types

import pytest

from benchmark import flops_mla_moe
from benchmark.drivers import model_train_cycles
from benchmark.tests.test_metrics import cycle, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash-ep16.json")))
M = CONFIG["model"]["config"]
TOKENS = 4 * 4096


def ctx(counters, cycles=None):
    return types.SimpleNamespace(
        stamps=dict(cycles=cycles or [cycle(100.0, 5.0, 0.0)], t_open=100.0, steps_per_cycle=10,
                    tokens_per_step=TOKENS, saves=False, counters=counters),
        trace=None, peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
        config=CONFIG, traffic=dict(params=dict(seq=4096, batch=4)),
        run=types.SimpleNamespace(chips=1), device=dict(memory_peak_bytes=12 * 2 ** 30))


def even_counters(steps=10):
    """An even router: 8 of 256 over 16 held experts, 5 expert layers."""
    here = steps * 5 * TOKENS * 8 * 16 // 256
    return {"moe.assignments_here": here, "moe.assignments_absent": steps * 5 * TOKENS * 8 - here,
            "moe.load_max_over_mean": 1.25 * steps * 5, "moe.layer_steps": steps * 5,
            "moe.dropped": 0, "train.steps_counted": steps}


def test_operations_from_shapes():
    # the issue's arithmetic: 26.35M in latent attention, 881 MFLOP a token forward
    assert flops_mla_moe.latent_projection_params(M) == 26_345_472
    assert flops_mla_moe.attention_layers(M) == 6 and flops_mla_moe.expert_layers(M) == 5
    assert flops_mla_moe.expert_mlp_flops(M) == 3 * 2 * 2048 * 768
    assert flops_mla_moe.score_flops_per_token(M, 4096) == 2 * 4096 * 320 * 32 / 2
    forward = flops_mla_moe.forward_flops_per_token(M, 4096, 0.5)
    assert forward == pytest.approx(881.07e6, rel=1e-4)
    assert flops_mla_moe.train_flops_per_token(M, 4096, 0.5) == 3 * forward
    # forward 2 products (320 wide in all), backward 5 (832): T^2 / 2 each
    per_width = 2 * 4 * 32 * 4096 * 4096 / 2
    assert flops_mla_moe.mla_flash_flops(M, 4, 4096) == per_width * (320 + 832)
    assert flops_mla_moe.mla_flash_bytes(M, 4, 4096) == 4 * 32 * 4096 * 2 * (640 + 768 + 512)
    assert flops_mla_moe.moe_gmm_flops(M, 1000) == 3 * 1000 * 3 * 2 * 2048 * 768
    assert flops_mla_moe.moe_gmm_bytes(M, 5) == 3 * 5 * 16 * 3 * 2048 * 768 * 2


def test_counter_readers():
    c = ctx(even_counters())
    assert reader("layer_metrics", "moe_load_max_over_mean")(c) == pytest.approx(1.25)
    # 16,384 tokens x 8 / 256 = 512 a held expert a step: 1/16 of the deployment's 8,192
    assert reader("layer_metrics", "moe_assignments_per_expert")(c) == pytest.approx(512.0)
    # 10 steps of 16,384 tokens in 5 s, 2.643 GFLOP a token, over 197 TFLOP/s
    mfu = reader("layer_metrics", "train_mfu_share")(c)
    assert mfu == pytest.approx(100 * 3 * 881.07e6 * (10 * TOKENS / 5.0) / 197e12, rel=1e-4)
    assert 0 < mfu < 100


def test_gmm_roofline_sets_the_traced_steps_work_against_their_time():
    """20 traced steps at an even load, whose grouped products took 12 ms a
    step; the window's own counters, at three times that load, move nothing."""
    c = ctx({**even_counters(50), "moe.assignments_here": 3 * even_counters(50)["moe.assignments_here"]})
    c.stamps["counters_traced"] = even_counters(20)
    events = {"%gmm.7 = bf16[32768,768]{1,0} custom-call(...)": (20 * 0.008,),
              "%tgmm.3 = (bf16[16,2048,768]{2,1,0}) custom-call(...)": (20 * 0.004,),
              "%tgmm.9 = bf16[128,128,2048]{2,1,0} custom-call(...)": (20 * 0.050,),  # row collecting
              "%fusion.1 = bf16[4,4096,2048]{2,1,0} fusion(...)": (20 * 0.3,)}
    c.trace = types.SimpleNamespace(used_planes=lambda: ["/device:TPU:0"], op_seconds=lambda: events,
                                    main_module=lambda: ("jit_step_fn", list(range(20))))
    per_step = 5 * TOKENS * 8 * 16 // 256
    least = max(flops_mla_moe.moe_gmm_flops(M, per_step) / 197e12,
                flops_mla_moe.moe_gmm_bytes(M, 5) / 819e9)
    got = reader("layer_metrics", "moe_gmm_roofline")(c)
    assert got == pytest.approx(100 * least / 0.012, rel=1e-6) and 0 < got < 100
    c.stamps["counters_traced"] = None  # an untraced run, or a worker that keeps no such totals
    assert reader("layer_metrics", "moe_gmm_roofline")(c) is None


@pytest.mark.parametrize("name", ["moe_load_max_over_mean", "moe_assignments_per_expert",
                                  "train_mfu_share", "moe_gmm_roofline", "mla_flash_roofline"])
def test_nothing_to_read_is_none(name):
    """Another driver's stamps (no counters), or no trace: the metric is left out."""
    bare = ctx(None)
    bare.stamps.pop("counters")
    assert reader("layer_metrics", name)(bare) is None
    gpt = ctx({})
    gpt.config = dict(gpt_config={})
    assert reader("layer_metrics", name)(gpt) is None


def test_first_step_is_held_to_each_limit():
    want = dict(trunk_loss=9.7, mtp_loss=9.7, grad_norm=2.0, assignments_here_by_layer=[8000, 8200])
    expected = dict(first_step=dict(values={"b4x4096": want}, tolerances=dict(
        trunk_loss=1e-3, mtp_loss=1e-3, grad_norm_rel=1e-2, assignments_rel=1e-2)))
    run = types.SimpleNamespace(config=dict(expected=expected), traffic=dict(params=dict(seq=4096)))

    def verdict(**moved):
        got = {"train.trunk_loss": 9.7, "train.mtp_loss": 9.7, "grad_norm": 2.0,
               "moe.assignments_here_by_layer": [8000, 8200], **moved}
        checks = {}
        model_train_cycles.check_first_step(
            run, dict(start_step=0, tokens_per_step=TOKENS, first_step=got), checks)
        return {k for k, v in checks.items() if v is False}

    assert verdict() == set()
    assert verdict(**{"train.trunk_loss": 9.702}) == {"trunk_loss_ok"}
    assert verdict(**{"train.mtp_loss": 9.698}) == {"mtp_loss_ok"}
    assert verdict(grad_norm=2.03) == {"grad_norm_ok"}
    assert verdict(**{"moe.assignments_here_by_layer": [8000, 8300]}) == {"assignments_ok"}
    assert verdict(**{"moe.assignments_here_by_layer": [8000]}) == {"assignments_ok"}


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's entry is in the file under its key;
    only the keys in ``reduced`` differ; the model as run has them too."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    published = next(json.loads(l) for l in open(catalog) if '"JoyAI-LLM-Flash"' in l)["config"]
    assert CONFIG["source"] == "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
    for key, value in published.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
        if key in M and key not in ("num_hidden_layers", "vocab_size", "n_routed_experts"):
            assert M[key] == value, key
    assert M["n_routed_experts"] == 256 and M["experts_held"] == CONFIG["n_routed_experts"] == 16
    assert M["vocab_size"] == CONFIG["vocab_size"] == 129280 // 8
    assert M["num_hidden_layers"] == CONFIG["num_hidden_layers"] == 5
