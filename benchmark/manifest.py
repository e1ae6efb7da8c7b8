"""BENCHMARK.json and the data files it names.

A cell is ``<config>.<traffic>``. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found by name, so a later PR adds a cell by adding files and manifest
entries and edits nothing here:

    <file of the config's entry>              sizes, server settings, and
                                              "architecture": <architecture>
    <path>/architectures/<architecture>.py    the served family, its kwargs,
                                              its comparison with the plain
                                              reference, its cost arithmetic
    <path>/traffic/<traffic>.json             the mix the generator reads
    <path>/layer_metrics/<metric>.py          a reader: read(run) -> value

Nothing outside an architecture's module knows a key of a published
config but ``vocab_size`` (the traffic draws token ids from it) and the
``server`` block (slots, max_seq).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# the driver's switch: cells are measured and traced in one process
# (``--trace 2``); read by nothing here
OPTIONAL_TOP_KEYS = {"trace_in_run"}


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, does not hold what it must."""


def load(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e
    validate(manifest)
    return manifest


def validate(manifest: dict) -> None:
    """The rules a typo breaks: key sets, names, units, references. The
    driver checks the whole contract; this catches a bad entry before a
    run is spent on it."""
    if set(manifest) - OPTIONAL_TOP_KEYS != TOP_KEYS:
        raise ManifestError(f"keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    seen: set = set()
    configs = {c["name"] for c in manifest["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            name = entry.get("name", "")
            if not NAME_RE.match(name):
                raise ManifestError(f"{group}: bad name {name!r}")
            if (group, name) in seen:
                raise ManifestError(f"{group}: {name!r} appears twice")
            seen.add((group, name))
    cells = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            raise ManifestError(f"cell {w['name']}: no config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            raise ManifestError(f"cell {w['name']}: bad traffic name")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips must be 1 or 4")
        cells.add(w["name"])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if not UNIT_RE.match(m.get("unit", "")):
                raise ManifestError(f"{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better must be lower|higher")
            if m.get("source") not in SOURCES:
                raise ManifestError(f"{m['name']}: bad source")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    raise ManifestError(f"{m['name']}: no cell {cell!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: end-to-end source")
        if not 0 < m.get("bound", 0) <= 0.1:
            raise ManifestError(f"{m['name']}: bound outside (0, 0.1]")
    for m in manifest["per_layer"]:
        if m.get("moves") not in e2e:
            raise ManifestError(f"{m['name']}: moves {m.get('moves')!r}")
        if not m.get("layer"):
            raise ManifestError(f"{m['name']}: no layer")


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no cell {name!r}; have {[w['name'] for w in manifest['workloads']]}"
    )


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The cell's metrics of one group: those without a ``workloads`` key,
    and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def config(root: str, manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            path = os.path.join(root, c["file"])
            cfg = _read_json(path)
            if not NAME_RE.match(str(cfg.get("architecture", ""))):
                raise ManifestError(
                    f'{path}: no "architecture" key: it names the module '
                    "<path>/architectures/<architecture>.py that holds this "
                    "configuration's served family, reference and cost "
                    "arithmetic; no default is taken")
            cfg["name"] = name
            return cfg
    raise ManifestError(f"no config {name!r}")


def _find(root: str, manifest: dict, *parts: str) -> str:
    for p in manifest["paths"]:
        path = os.path.join(root, p, *parts)
        if os.path.isfile(path):
            return path
    raise ManifestError(f"no {os.path.join(*parts)} under {manifest['paths']}")


def traffic(root: str, manifest: dict, name: str) -> dict:
    mix = _read_json(_find(root, manifest, "traffic", name + ".json"))
    mix["name"] = name
    return mix


def peaks(root: str, manifest: dict, device_kind: str) -> dict:
    table = _read_json(_find(root, manifest, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"device {device_kind!r} is not in the table of peaks "
            f"({sorted(table)}): add it with its source, no default is taken"
        )
    return table[device_kind]


def layer_reader(root: str, manifest: dict, metric: str):
    """The ``read(run)`` function of ``layer_metrics/<metric>.py``."""
    path = _find(root, manifest, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"\W", "_", metric), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# what an architecture's module must hold, and run.py, child.py and the
# roofline readers call
ARCHITECTURE_API = ("FAMILY", "register", "model_kwargs", "compare_served",
                    "decode_step_bytes", "prefill_flops", "rehearsal")


def architecture(root: str, manifest: dict, name: str):
    """The module ``architectures/<name>.py`` of a configuration's
    ``"architecture"``, loaded once a process:

    ``FAMILY``, ``register()``      the served family, through the program's
                                    own ``models.register``
    ``model_kwargs(cfg, seed)``     the ``config`` of ``jax_config.json``
    ``compare_served(model, params, seed)``
                                    the served model against its plain
                                    reference; replies ``ok``, ``ratio``,
                                    ``tolerance``, ``finite``, ...
    ``decode_step_bytes(cfg, live_positions, counters)``
    ``prefill_flops(cfg, padded_tokens, sequences, counters)``
                                    what a step must read and a prefill must
                                    compute; ``counters`` as
                                    ``capture.counters`` gives them
    ``rehearsal(cfg)``              the tiny sizes of ``--rehearse-cpu``

    The parent loads it too and never imports jax: the module imports jax
    and the program inside its functions.

    It is imported by the dotted name of its path under ``root`` (which is
    on ``sys.path`` in the parent and in the child), so that the program's
    ``models.register`` can name the served class. The parent calls this;
    the child is given ``module.__name__`` and imports that and nothing
    else: whatever more the child ran before the engine started (this
    function, ``load``, a few thousand objects made and dropped) made every
    lowering of the decode burst an eighth slower there (5.4 -> 6.1 s each,
    ``warm_s`` +9 s; my chip runs, PR 27)."""
    path = _find(root, manifest, "architectures", name + ".py")
    dotted = os.path.splitext(os.path.relpath(path, root))[0].replace(os.sep, ".")
    try:
        module = importlib.import_module(dotted)
    except ImportError as e:
        raise ManifestError(f"{path}: import {dotted}: {e}") from e
    if not os.path.samefile(module.__file__, path):
        raise ManifestError(
            f"{dotted} is {module.__file__}, not {path}: another copy of the "
            "benchmark comes first on sys.path")
    lacks = [a for a in ARCHITECTURE_API if not hasattr(module, a)]
    if lacks:
        raise ManifestError(f"{path}: lacks {lacks}")
    return module


def decoder_kwargs(cfg: dict, seed: int) -> dict:
    """The ``decoder`` module's ``model_kwargs`` under the name that
    ``tools/burst_hlo_check.py`` imports: the tool lies outside the
    benchmark's paths, where the PR that moved the function may not edit.
    Goes once the tool asks ``architecture(...)`` itself (PERF.md, section 7)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return architecture(root, load(root), "decoder").model_kwargs(cfg, seed)
