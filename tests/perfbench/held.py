"""What ``BENCHMARK.json`` has to keep, as functions of a manifest.

A later PR adds a cell, a configuration, a mix or a per-layer metric by
adding files and manifest entries, and may edit none that is there. So what
these functions hold is a PREFIX, never the whole: an earlier PR's entries
are there, in their places and unedited, and anything may follow them. A
configuration or a cell is held by its name and its index, a per-layer
metric's ``workloads`` list by its first entries, a cell's metrics as a
superset, a count as ``>=``. A PR's own tests hold ITS entries the same way,
by name, never by ``[-1]``, a total, an open slice (``[5:]``) or a
``workloads`` list compared whole: a PR adds its own function here, to
``CHECKS``, and ``test_perfbench_additions.py`` applies every function here
to the committed manifest plus a synthetic addition, and to that on top of
another, so a function that pins a list fails there before it can refuse the
next PR.

Each function raises ``AssertionError`` on the first thing it finds wrong
and returns nothing. ``CHECKS`` lists them all.
"""

import os
import re

from perfbench.lib import manifest as manifest_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# PR 24: six serving metrics, each with a twin for the cells that report
# gap_p95_ms, and the flash kernel's share: per_layer[18:31]
SERVE = ("step.decode_ms", "step.prefill_share", "sched.queue_wait_ms",
         "sched.host_share", "sched.stall_share", "replica.stream_lag_ms")
FLASH = "kernel.flash_roofline"
THIRTEEN = [n + s for n in SERVE for s in ("", ".gap")] + [FLASH]
# PR 26: the eight lists the expert cell joined, behind the chat cell
SHARED = ("client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "kernel.paged_attn_roofline", "device.idle_share.gap")
# PR 31: the readers of the expert counters, per_layer[31:33]
MOE = ("moe.decode_step_roofline", "moe.max_expert_load")
FIRST_FREE = 31  # what follows PR 24's thirteen was appended, and is free


def _rows(manifest):
    return {m["name"]: m for m in manifest["per_layer"]}


def _one_line(text, most=200):
    return (isinstance(text, str) and 1 <= len(text) <= most
            and "\n" not in text and "\t" not in text)


# ----------------------------------------------- the contract's static rules


def static_rules(manifest):
    """Keys, names, units, bounds, the share of four-chip cells and the
    length of a run: what the driver refuses before any run."""
    assert set(manifest) - {"_dir"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    rows = (manifest["configs"] + manifest["workloads"]
            + manifest["end_to_end"] + manifest["per_layer"])
    for row in rows:
        assert NAME.match(row["name"]), row["name"]
    for group in ("configs", "workloads"):
        names = [r["name"] for r in manifest[group]]
        assert len(names) == len(set(names))
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES, m
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= ({"bound"} if m in manifest["end_to_end"]
                    else {"layer", "moves"})
        assert set(m) <= allowed, m
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert _one_line(m["layer"]), m
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in manifest["end_to_end"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["why"]) and _one_line(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(key) for key in c["reduced"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200, "run_seconds must fit with the full 24 cells"


def every_cell_reports(manifest):
    """Every configuration is used, a pair of configuration and traffic
    appears once, and every cell reports ``setup_s``, another end-to-end
    metric and per-layer metrics that move what the cell reports."""
    cells = {w["name"] for w in manifest["workloads"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m.get("workloads", True), m["name"]  # a list names a cell
    for cell in cells:
        e2e = {m["name"] for m in manifest_lib.metrics_for(
            manifest, cell, False)}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        per_layer = manifest_lib.metrics_for(manifest, cell, True)
        assert per_layer, cell
        for m in per_layer:
            assert m["moves"] in e2e_names
            assert m["moves"] in e2e, (m["name"], cell)


def every_named_file_is_there(manifest):
    """A configuration's file, its family and reference, a cell's file, a
    mix's file and a metric's reader, each found by the name alone."""
    root = manifest["_dir"]
    bench = manifest_lib.bench_dir(manifest)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith(manifest["paths"][0] + "/")
        cfg = manifest_lib.config(manifest, c["name"])
        fam = manifest_lib.read_json_from_bench("families",
                                                cfg["model_type"])
        assert os.path.isfile(os.path.join(
            bench, "reference", fam["reference"] + ".py"))
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"], key
    for w in manifest["workloads"]:
        assert manifest_lib.read_json(manifest, "cells", w["name"])["kind"]
        assert manifest_lib.read_json(manifest, "traffic", w["traffic"])
    for m in manifest["per_layer"]:
        assert callable(manifest_lib.metric_reader(m["name"]))
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.isfile(os.path.join(root, manifest["command"][1]))


# ------------------------------------------------- what earlier PRs listed


def pr24_entries(manifest):
    """The thirteen names at ``per_layer[18:31]``; each serving metric in
    the docs cell first and its twin in the chat cell first; the flash
    share in the two training cells first."""
    rows = _rows(manifest)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[18:FIRST_FREE] == THIRTEEN
    for name in SERVE:
        assert rows[name]["workloads"][:1] == ["mistral7b_docs"]
        assert rows[name]["moves"] == "serve_tokens_per_s"
        twin = rows[name + ".gap"]
        assert twin["workloads"][:1] == ["mistral7b_chat"]
        assert twin["moves"] == "gap_p95_ms"
        assert {k: v for k, v in twin.items()
                if k not in ("name", "workloads", "moves")} == \
            {k: v for k, v in rows[name].items()
             if k not in ("name", "workloads", "moves")}
    assert rows[FLASH]["workloads"][:2] == ["gpt2s_train",
                                            "mistral7b_train_4chip"]
    for name in THIRTEEN:
        assert callable(manifest_lib.metric_reader(name))
    layers_of = {m["layer"] for m in manifest["per_layer"][:18]}
    assert {rows[n]["layer"] for n in THIRTEEN} <= layers_of


def pr26_config(manifest):
    """The expert configuration, fourth, cut in depth only."""
    entry = manifest["configs"][3]
    assert entry["name"] == "olmoe_1b_7b_l8"
    assert entry["reduced"] == ["num_hidden_layers"]
    hp = manifest_lib.config(manifest, "olmoe_1b_7b_l8")
    assert entry["source"] == hp["source"] and "allenai" in entry["source"]
    assert [c["name"] for c in manifest["configs"][:3]] == [
        "gpt2_small", "mistral7b_v03_l16", "mistral7b_v03_l8"]


def pr26_cell(manifest):
    """The expert cell, fifth, behind the four of PR 23; it reports
    ``gap_p95_ms`` and joined the eight lists the chat cell was in."""
    assert [w["name"] for w in manifest["workloads"][:5]] == [
        "gpt2s_train", "mistral7b_chat", "mistral7b_docs",
        "mistral7b_train_4chip", "olmoe_reason"]
    cell = manifest["workloads"][4]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe_1b_7b_l8", "reason", 1)
    e2e = {m["name"] for m in manifest_lib.metrics_for(
        manifest, "olmoe_reason", False)}
    assert e2e >= {"gap_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in manifest_lib.metrics_for(
        manifest, "olmoe_reason", True)}
    assert per_layer >= set(SHARED) | {"own.worker_start_s"}
    rows = _rows(manifest)
    for name in SHARED:  # appended to, nothing else changed
        assert rows[name]["workloads"][:2] == ["mistral7b_chat",
                                               "olmoe_reason"]


def pr31_entries(manifest):
    """The expert cell in PR 24's six twins, behind the chat cell, and the
    two readers of the expert counters at ``per_layer[31:33]``."""
    rows = _rows(manifest)
    for name in SERVE:
        assert rows[name + ".gap"]["workloads"][:2] == ["mistral7b_chat",
                                                        "olmoe_reason"]
    assert [m["name"] for m in manifest["per_layer"][31:33]] == list(MOE)
    for name in MOE:
        assert rows[name]["workloads"][:1] == ["olmoe_reason"]
        assert (rows[name]["moves"], rows[name]["layer"]) == (
            "gap_p95_ms", "experts")
    gone = {n + ".moe" for n in ("step.decode_ms", "step.prefill_share",
                                 "sched.host_share")}
    assert not gone & set(rows)  # the twins the pin had forced


def appended_entries(manifest):
    """Whatever follows PR 24's thirteen: a reader, a ``workloads`` list,
    a layer on one line and a ``moves`` that each of its cells reports."""
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"][FIRST_FREE:]:
        assert callable(manifest_lib.metric_reader(m["name"])), m["name"]
        assert m.get("workloads") and set(m["workloads"]) <= cells, m
        assert _one_line(m["layer"]), m
        for cell in m["workloads"]:
            e2e = {e["name"] for e in manifest_lib.metrics_for(
                manifest, cell, False)}
            assert m["moves"] in e2e, (m["name"], cell)


CHECKS = (static_rules, every_cell_reports, every_named_file_is_there,
          pr24_entries, pr26_config, pr26_cell, pr31_entries,
          appended_entries)


# ------------------------------------------------------- one PR's addition


def only_added(old, new):
    """``new`` is ``old`` plus additions: every list of ``old`` is a prefix
    of ``new``'s, and an entry differs only by cells appended to its
    ``workloads``. What a PR that is no ``benchmark`` PR may do."""
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key], key
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[group]) >= len(old[group]), group
        for was, now in zip(old[group], new[group]):
            assert {**now, "workloads": 0} == {**was, "workloads": 0}, \
                was["name"]
            if "workloads" in was:
                n = len(was["workloads"])
                assert now["workloads"][:n] == was["workloads"], was["name"]
            else:
                assert "workloads" not in now, was["name"]
