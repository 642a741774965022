"""Discovery by name, and the shape of ``BENCHMARK.json``."""
import json
import os
import re

import pytest

from bench.lib import spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_unknown_workload_fails():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no_such.cell")


def test_unknown_metric_reader_fails():
    with pytest.raises(spec.SpecError, match="no metrics file"):
        spec.load_module("metrics", "no_such.metric")


def test_unknown_device_kind_fails():
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_name_that_leaves_the_directory_fails():
    with pytest.raises(spec.SpecError, match="not a benchmark name"):
        spec.load_module("metrics", "../run")


def test_cell_with_missing_reader_fails(tmp_path, monkeypatch):
    b = json.loads(json.dumps(BENCH))
    b["per_layer"].append(dict(b["per_layer"][0], name="engine.nothing"))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    with pytest.raises(spec.SpecError, match="engine.nothing"):
        spec.load_cell(CELLS[0], str(path))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_and_reports_enough(cell):
    c = spec.load_cell(cell)
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert c.limits()
    for m in c.per_layer:
        reader = spec.load_module("metrics", m.name)
        assert (reader.SOURCE, reader.UNIT) == (m.source, m.unit)
        assert m.moves in names


def test_names_units_and_lengths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], 0)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_config_files_name_their_cuts():
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg.get("published", {})) == set(c["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not (k.endswith("_dim") or k.endswith("_rank"))


def test_kimi_config_keeps_every_published_width():
    cfg = json.load(open(os.path.join(spec.BENCH, "configs",
                                      "kimi_k2_ep24.json")))
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
              "num_experts_per_tok"):
        assert k not in cfg["reduced"]
    assert cfg["hidden_size"] == 7168 and cfg["num_experts_per_tok"] == 8
    # the YaRN group is cut at its factor alone; its widths stay published
    rope, published = cfg["rope_scaling"], cfg["published"]["rope_scaling"]
    assert set(rope) == set(published)
    assert {k for k in rope if rope[k] != published[k]} == {"factor"}
    assert rope["mscale_all_dim"] == published["mscale_all_dim"]


def _kimi():
    return json.load(open(os.path.join(spec.BENCH, "configs",
                                       "kimi_k2_ep24.json")))


@pytest.mark.parametrize("kind", ["drivers", "configs"])
def test_scaled_rope_is_refused(kind):
    """Refused by the program's fields the driver builds on, and by the
    reference."""
    cfg = _kimi()
    cfg["rope_scaling"] = cfg["published"]["rope_scaling"]
    mod = spec.load_module("configs", "kimi_k2_ep24")
    read = mod.program_fields if kind == "drivers" else mod.sizes
    with pytest.raises(ValueError, match="unscaled RoPE"):
        read(cfg)


@pytest.mark.parametrize("key,value", [
    ("first_k_dense_replace", 1), ("scoring_func", "sigmoid"),
    ("norm_topk_prob", True), ("routed_scaling_factor", 2.827),
    ("hidden_act", "gelu"), ("attention", None), ("attention", "mla")])
def test_what_the_program_does_not_run_is_refused(key, value):
    cfg = _kimi()
    where = cfg["assumed"] if key == "attention" else cfg
    if value is None:
        del where[key]
    else:
        where[key] = value
    with pytest.raises(ValueError, match=key):
        spec.load_module("configs", "kimi_k2_ep24").program_fields(cfg)


def test_kimi_program_config_is_the_fixed_mapping():
    """The ``ArchConfig`` the serve driver builds for Kimi K2 is, field by
    field, the one it built before the configuration stated its fields."""
    import dataclasses

    from repro.configs.base import get
    ref = spec.load_module("configs", "kimi_k2_ep24")
    got = spec.load_module("drivers", "serve").program_arch(_kimi(), ref)
    want = dataclasses.replace(
        get("kimi_k2_1t_a32b"), n_layers=4, d_model=7168, n_heads=64,
        n_kv_heads=8, vocab=20480, n_experts=16, top_k=8, moe_d_ff=2048,
        n_shared_experts=1, rope_theta=10000.0, head_dim=112,
        capacity_factor=1.25, dtype="bfloat16")
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    assert type(got.rope_theta) is float


@pytest.mark.parametrize("missing", ["program_fields", "kernel_calls",
                                     "token_flops"])
def test_config_module_without_a_needed_function_fails(missing,
                                                       monkeypatch):
    load = spec.load_module

    def without(kind, name):
        mod = load(kind, name)
        if kind == "configs":
            mod.__dict__.pop(missing)
        return mod

    monkeypatch.setattr(spec, "load_module", without)
    with pytest.raises(spec.SpecError, match=missing):
        spec.load_cell(CELLS[0])
