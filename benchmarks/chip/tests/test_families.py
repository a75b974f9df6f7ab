"""A model family is a file in ``families/``, chosen by the stated
``model["family"]``: the dense one gives what ``reference.py`` and
``counts.py`` give, a stated key is checked by the shared fields or the
family's own, and a family with no file is an error that names it."""

import json
import types

import numpy as np
import pytest

from benchmarks.chip import counts, readers, reference, spec, system
from benchmarks.chip.peaks import Peak

V5E = Peak(bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9, source="test")


@pytest.fixture(scope="module")
def seeded_yi():
    """A reduced yi-6b as a rehearsal runs it: its stated sizes and seeded
    weights, with prompts and served tokens drawn from the seed."""
    from benchmarks.chip import weights

    config = spec.load().cell("yi-6b.decode-b32").config
    cfg = system.arch_config(config, rehearsal=True)
    model = system.stated_model(config, cfg, rehearsal=True)
    params = weights.make(system.abstract_params(cfg), 2**33 + 17)
    rng = np.random.default_rng(17)
    prompts = rng.integers(0, cfg.vocab_size, (3, 24), dtype=np.int32)
    served = rng.integers(0, cfg.vocab_size, (3, 6), dtype=np.int32)
    return model, params, prompts, served


def test_dense_gaps_are_the_reference(seeded_yi):
    from benchmarks.chip.drivers.serve_offline import _gaps

    model, params, prompts, served = seeded_yi
    assert model["family"] == "dense"
    want = np.asarray(reference.served_gaps(model, params, prompts, served))
    assert np.array_equal(_gaps(model, params, prompts, served), want)
    want = np.asarray(reference.control_gaps(model, params, prompts, served))
    assert np.array_equal(_gaps(model, params, prompts, served, control=True), want)


def test_dense_flops_are_the_counts(seeded_yi):
    model = seeded_yi[0]
    traffic = {"batch": 4, "prompt_len": 24, "gen_buckets": [6]}
    run = {"model": model, "traffic": traffic, "peak": V5E,
           "prefill_s": [0.5, 0.25], "decode_s": [0.125]}
    assert readers.mfu_prefill(run) == 100.0 * counts.prefill_flops(model, 4, 24) * 2 / 0.75 / 197e12
    assert readers.mfu_decode(run) == 100.0 * counts.decode_flops(model, 4, 24, 6) / 0.125 / 197e12


def _qwen3_moe_file(tmp_path, **model_keys) -> dict:
    """A configuration file for registry ``qwen3-moe-235b-a22b`` that
    states the program's own sizes, but for ``model_keys``."""
    from repro.configs.registry import get_arch

    cfg = get_arch("qwen3-moe-235b-a22b")
    model = {k: getattr(cfg, f) for k, f in system.fields("moe").items()} | model_keys
    path = tmp_path / "qwen3-moe.json"
    path.write_text(json.dumps({"arch": cfg.name, "overrides": {}, "reduced": [], "model": model}))
    return json.loads(path.read_text())


@pytest.fixture
def moe_family(monkeypatch):
    """A MoE family that states its expert count and experts per token."""
    moe = types.SimpleNamespace(FIELDS={"n_experts": "n_experts",
                                        "experts_per_token": "experts_per_token"})
    real = spec.load_family
    monkeypatch.setattr(spec, "load_family", lambda name: moe if name == "moe" else real(name))


def test_arch_config_checks_the_family_keys(tmp_path, moe_family):
    assert system.arch_config(_qwen3_moe_file(tmp_path)).n_experts == 128
    with pytest.raises(ValueError, match="n_experts"):
        system.arch_config(_qwen3_moe_file(tmp_path, n_experts=64))


def test_arch_config_refuses_a_key_with_no_field(tmp_path, moe_family):
    with pytest.raises(ValueError, match=r"no ArchConfig field \['n_shared_experts'\]"):
        system.arch_config(_qwen3_moe_file(tmp_path, n_shared_experts=0))


def test_positions_are_checked_where_not_stated(tmp_path, moe_family):
    """Rotary positions and no softcap, unless the file states otherwise
    and the program agrees."""
    assert system.arch_config(_qwen3_moe_file(tmp_path, pos_embed="rope", attn_softcap=0.0))
    with pytest.raises(ValueError, match="attn_softcap"):
        system.arch_config(_qwen3_moe_file(tmp_path, attn_softcap=30.0))


def test_unknown_family_names_the_file_to_add():
    config = {"arch": "qwen3-moe-235b-a22b", "overrides": {}, "model": {"family": "moe"}}
    with pytest.raises(LookupError, match="add benchmarks/chip/families/moe.py"):
        system.arch_config(config)
    run = {"model": {"family": "moe"}, "traffic": {"batch": 1, "prompt_len": 8},
           "prefill_s": [1.0], "peak": V5E}
    with pytest.raises(LookupError, match="add benchmarks/chip/families/moe.py"):
        readers.mfu_prefill(run)
