"""Each configuration's cut keeps its source's published widths, runs as
its file states, and lists every key it changed.

The published widths of each source's config.json are a file of their
own, ``data/published/<config>.json``, which a configuration brings with
it."""
import json
import pathlib

import pytest

from yardstick import harness

PUBLISHED = pathlib.Path(__file__).resolve().parent / "data" / "published"
WIDTHS = ("hidden_size", "intermediate_size", "head_dim")
BENCH = harness.benchmark()
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def _file(name):
  return json.loads((harness.CHECKOUT / CONFIGS[name]["file"]).read_text())


def _published(name):
  path = PUBLISHED / f"{name}.json"
  assert path.exists(), (f"configuration {name} brings its source's "
                         f"published widths in {path}")
  return json.loads(path.read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_cut_keeps_the_published_widths(name):
  conf, published = _file(name), _published(name)
  for key, value in published.items():
    assert conf[key] == value, key
  cfg = harness.model_config(conf)
  assert (cfg.d_model, cfg.d_ff, cfg.hd) == tuple(
      published[k] for k in WIDTHS)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reduced_lists_exactly_the_changed_keys(name):
  conf = _file(name)
  reduced = CONFIGS[name]["reduced"]
  assert sorted(reduced) == sorted(conf["published"])
  assert all(conf[k] != v for k, v in conf["published"].items())
  widths = WIDTHS + ("num_experts_per_tok",)
  assert not any(k in widths or k.endswith(("_dim", "_rank")) for k in reduced)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_file_is_what_runs(name):
  conf = _file(name)
  conf = dict(conf, num_hidden_layers=conf["num_hidden_layers"] + 1)
  with pytest.raises(ValueError, match="num_hidden_layers"):
    harness.model_config(conf)


def test_every_cell_has_its_files():
  for w in BENCH["workloads"]:
    cell = harness.load_cell(w["name"], BENCH)
    assert {"latency_p95_ms", "ttft_p50_ms", "setup_s"} <= set(
        cell.end_to_end), w["name"]
    assert cell.per_layer
    for m in cell.per_layer:
      assert (harness.CHIP / "metrics" / f"{m}.py").exists()
