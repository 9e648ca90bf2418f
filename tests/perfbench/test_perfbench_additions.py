"""The benchmark can be added to by adding (ISSUE 31).

A later PR's tree is built here in a temporary directory: the committed
``perfbench/`` copied, then a configuration (the expert one under another
name), a one-chip cell that reports ``gap_p95_ms`` and joins every list
``olmoe_reason`` is in, a traffic mix and one appended per-layer metric with
its reader, each as a NEW file, and ``BENCHMARK.json`` with the matching
entries appended. Every function of ``held.py`` (the contract's static
rules, and what each earlier PR listed) has to accept that tree, and has to
go on refusing one in which an earlier entry was edited, moved or taken away.

Every case runs twice: with the addition made to the committed manifest, and
made to a manifest to which a PR in between has already appended its own
configuration, cell and metric. The second is this file's own successor: what
is asserted here names the added entries and finds their places from the
lengths of the manifest they were added to, so the next real addition to
``BENCHMARK.json`` fails nothing here. Structure only: nothing here is a
device number.
"""

import copy
import filecmp
import json
import os
import shutil

import pytest

from perfbench.lib import contract
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held

HERE = os.path.dirname(os.path.abspath(__file__))
COMMITTED = manifest_lib.load()
NEIGHBOUR = "olmoe_reason"  # the added cell joins every list this one is in
TAG = "added"  # the PR under test; a PR in between is "earlier"
BASES = {"on_the_committed_manifest": (),
         "on_a_manifest_a_pr_has_added_to": ("earlier",)}


def names_of(tag):
    return {"config": f"{tag}_config", "cell": f"{tag}_cell",
            "mix": f"{tag}_mix", "metric": f"{tag}.reading"}


CONFIG, CELL, MIX, METRIC = (names_of(TAG)[k] for k in (
    "config", "cell", "mix", "metric"))


def rows_by_name(rows):
    return {r["name"]: r for r in rows}


def add_a_prs_entries(manifest, tag):
    """The entries a PR appends for one configuration, one cell and one
    metric; nothing that is there is touched but the ``workloads`` lists
    the new cell joins, at their ends."""
    new = names_of(tag)
    like = rows_by_name(manifest["configs"])["olmoe_1b_7b_l8"]
    manifest["configs"].append(dict(
        like, name=new["config"],
        file=f"perfbench/configs/{new['config']}.json"))
    manifest["workloads"].append({
        "name": new["cell"], "config": new["config"], "traffic": new["mix"],
        "chips": 1, "why": "one more cell, as a later PR would add it"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if NEIGHBOUR in m.get("workloads", ()):
            m["workloads"].append(new["cell"])
    manifest["per_layer"].append({
        "name": new["metric"], "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "gap_p95_ms", "workloads": [new["cell"]]})
    return manifest


def add_a_prs_files(bench, tag):
    new = names_of(tag)
    for kind, old, name in (("configs", "olmoe_1b_7b_l8", new["config"]),
                            ("cells", NEIGHBOUR, new["cell"]),
                            ("traffic", "reason", new["mix"])):
        shutil.copy(os.path.join(bench, kind, old + ".json"),
                    os.path.join(bench, kind, name + ".json"))
    with open(os.path.join(bench, "metrics", new["metric"] + ".py"),
              "w") as f:
        f.write('"""A reading a later PR adds."""\n\n\n'
                "def read(ctx):\n"
                '    return ctx["counters"]["delta"].get("admitted")\n')


@pytest.fixture(scope="module", params=sorted(BASES))
def trees(request, tmp_path_factory):
    """The tree of a PR that added: files copied, files added, none
    edited. Returns the path of its ``perfbench/``, and the manifest it
    added to (the committed one, or that plus a PR in between)."""
    root = tmp_path_factory.mktemp("later_pr")
    bench = os.path.join(root, "perfbench")
    shutil.copytree(manifest_lib.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for tag in BASES[request.param]:
        add_a_prs_files(bench, tag)
        add_a_prs_entries(manifest, tag)
    base = copy.deepcopy(manifest)
    add_a_prs_files(bench, TAG)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(add_a_prs_entries(manifest, TAG), f, indent=2)
    return bench, base


@pytest.fixture
def base(trees):
    return trees[1]


@pytest.fixture
def later(trees, monkeypatch):
    """The later PR's manifest, with the yardstick's own files (families,
    readers) looked up in its tree."""
    monkeypatch.setattr(manifest_lib, "BENCH_DIR", trees[0])
    return manifest_lib.load(os.path.join(os.path.dirname(trees[0]),
                                          "BENCHMARK.json"))


@pytest.mark.parametrize("check", held.CHECKS, ids=lambda f: f.__name__)
def test_a_manifest_with_a_later_prs_additions_is_held_and_passes(check,
                                                                  later):
    check(COMMITTED)
    check(later)


def test_the_addition_edits_no_entry_and_no_file(later, base, trees):
    held.only_added(COMMITTED, later)
    held.only_added(base, later)
    # each added entry right behind what was there, whatever that was
    assert later["configs"][len(base["configs"])]["name"] == CONFIG
    assert later["workloads"][len(base["workloads"])]["name"] == CELL
    assert later["per_layer"][len(base["per_layer"])]["name"] == METRIC
    for m in base["end_to_end"] + base["per_layer"]:
        if NEIGHBOUR in m.get("workloads", ()):
            now = rows_by_name(
                later["end_to_end"] + later["per_layer"])[m["name"]]
            assert now["workloads"][len(m["workloads"])] == CELL
    committed = os.path.join(COMMITTED["_dir"], "perfbench")
    for folder, _, names in os.walk(committed):
        if "__pycache__" in folder:
            continue
        there = os.path.join(trees[0], os.path.relpath(folder, committed))
        for name in names:
            assert filecmp.cmp(os.path.join(folder, name),
                               os.path.join(there, name), shallow=False), name


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "traced"])
def test_the_added_cell_reports_what_its_neighbour_does_and_its_own(traced,
                                                                    later):
    mine = [m["name"] for m in manifest_lib.metrics_for(later, CELL, traced)]
    theirs = [m["name"] for m in manifest_lib.metrics_for(
        later, NEIGHBOUR, traced)]
    assert mine == theirs + ([METRIC] if traced else [])
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 13958643712}
    if traced:
        device.update(window_s=3.0, busy_s=2.9)
    units = {m["name"]: m["unit"] for m in
             later["end_to_end"] + later["per_layer"]}
    line = contract.build_line(
        correct=True, attempted=100, failed=0, device=device,
        metrics={n: {"value": 12.5, "unit": units[n]} for n in mine},
        breakdown={"device_ops": [], "idle_gaps": []} if traced else None)
    assert contract.check_line(line, later, CELL, traced) == []
    assert manifest_lib.metric_reader(METRIC)(
        {"counters": {"delta": {"admitted": 7}}}) == 7
    line["metrics"].pop(METRIC if traced else "gap_p95_ms")
    assert contract.check_line(line, later, CELL, traced)


def _per_layer(m, name):
    return rows_by_name(m["per_layer"])[name]


def _index(rows, name):
    return [r["name"] for r in rows].index(name)


def _drop_from(name, cell):
    def change(m):
        _per_layer(m, name)["workloads"].remove(cell)
    return change


def _swap(group, a, b):
    def change(m):
        i, j = _index(m[group], a), _index(m[group], b)
        m[group][i], m[group][j] = m[group][j], m[group][i]
    return change


def _first_in_its_list(name):
    def change(m):
        cells = _per_layer(m, name)["workloads"]
        cells.insert(0, cells.pop())
    return change


def _before_the_thirteen(m):
    rows = m["per_layer"]
    rows.insert(_index(rows, held.THIRTEEN[0]),
                rows.pop(_index(rows, METRIC)))


EDITS = {
    "a_metric_put_before_the_thirteen": _before_the_thirteen,
    "one_of_the_thirteen_taken_away":
        lambda m: m["per_layer"].pop(_index(m["per_layer"],
                                            held.THIRTEEN[2])),
    "the_expert_cell_out_of_a_list_it_joined":
        _drop_from("client.tokens_per_s", "olmoe_reason"),
    "the_chat_cell_out_of_a_twins_list":
        _drop_from("step.decode_ms.gap", "mistral7b_chat"),
    "a_new_cell_put_first_in_a_list":
        _first_in_its_list("step.decode_ms.gap"),
    "two_configurations_swapped":
        _swap("configs", "mistral7b_v03_l8", "olmoe_1b_7b_l8"),
    "two_cells_swapped":
        _swap("workloads", "mistral7b_train_4chip", "olmoe_reason"),
    "the_expert_readers_moved": _swap("per_layer", *held.MOE),
}
WRONG_ADDITIONS = {  # nothing that was there is touched: the rules refuse
    "an_appended_metric_without_a_list":
        lambda m: _per_layer(m, METRIC).pop("workloads"),
    "an_appended_metric_that_moves_what_its_cell_does_not_report":
        lambda m: _per_layer(m, METRIC).update(moves="train_tokens_per_s"),
    "an_appended_metric_without_a_reader":
        lambda m: _per_layer(m, METRIC).update(name="added.no_such_file"),
    "a_bound_over_a_tenth":
        lambda m: rows_by_name(m["end_to_end"])["setup_s"].update(bound=0.2),
}


def refusals(manifest):
    refused = []
    for check in held.CHECKS:
        try:
            check(manifest)
        except (AssertionError, KeyError, FileNotFoundError):
            refused.append(check.__name__)
    return refused


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_an_edit_of_what_is_there_is_still_refused(edit, later):
    edited = copy.deepcopy(later)
    EDITS[edit](edited)
    assert refusals(edited), edit
    with pytest.raises(AssertionError):
        held.only_added(COMMITTED, edited)


@pytest.mark.parametrize("edit", sorted(WRONG_ADDITIONS))
def test_an_addition_against_the_rules_is_refused(edit, later):
    edited = copy.deepcopy(later)
    WRONG_ADDITIONS[edit](edited)
    assert refusals(edited), edit


def test_the_added_cell_may_take_four_chips_only_within_the_quarter(later):
    """One four-chip cell always may; more only up to a quarter of the
    cells, rounded down: whether the added cell may is worked out from the
    manifest it joins, not from today's count of cells."""
    edited = copy.deepcopy(later)
    rows_by_name(edited["workloads"])[CELL].update(chips=4)
    four = sum(w["chips"] == 4 for w in edited["workloads"])
    allowed = four <= max(1, len(edited["workloads"]) // 4)
    assert (refusals(edited) == []) == allowed
    assert four >= 2  # the committed manifest has its own four-chip cell
