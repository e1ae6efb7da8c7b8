"""JAX hot-path hygiene: ``host-sync-hot-path`` and ``retrace-hazard``.

Scope: methods reachable (via same-class calls) from a scheduler hot
root — any ``@scheduler_only`` method, or a method named ``_loop`` /
``_run``. That is the code executing at poll cadence between device
dispatches, where a stray host sync serializes the software pipeline
and a retrace stalls every lane for seconds.

**host-sync-hot-path.** Implicit host syncs block the scheduler until
the device catches up:

* ``.item()`` / ``.block_until_ready()`` / ``jax.device_get`` anywhere
  in hot-path code — these are syncs by definition. The *designed* sync
  points (reading a finished burst's tokens) carry suppressions with
  justification, which is exactly the visibility we want.
* ``bool()`` / ``int()`` / ``float()`` / ``np.asarray()`` /
  ``np.array()`` applied to a value produced by a jitted callable in
  the same function (``self._burst_fn``-style attributes assigned from
  ``jax.jit`` in the class body). Tracking is intra-function
  assignment-based on purpose: a parameter or attribute could be
  anything, and guessing would bury real findings in noise.

**retrace-hazard.** Calls to those same jitted callables are checked at
their ``static_argnums`` positions: a static argument drawn from an
unbounded or unhashable domain re-specializes the executable per
distinct value —

* ``len(...)`` at a static position (unbounded integers; pass a pow2 /
  bucketized size instead, as ``_attn_need`` does),
* float constants or ``float()`` casts (continuous domain — e.g. a
  temperature must be a traced operand, not a static),
* dict/list/set literals (unhashable: ``jit`` rejects them at runtime,
  and hashable wrappers retrace per content).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .callgraph import ClassIndex, index_classes, reachable_set
from .core import Finding, LintContext, SourceFile

__all__ = ["check_host_sync", "check_retrace"]

_CAST_FNS = {"bool", "int", "float"}
_NP_SYNC = {"asarray", "array"}


def _hot_roots(cls: ClassIndex) -> List[str]:
    roots = [n for n, m in cls.methods.items() if m.role == "scheduler"]
    for name in ("_loop", "_run"):
        if name in cls.methods and name not in roots:
            roots.append(name)
    return roots


def _jit_result_names(fn: ast.AST, jit_attrs: Dict[str, Tuple[int, ...]]) -> Set[str]:
    """Names bound (directly or via tuple unpack) from a jitted call."""
    names: Set[str] = set()
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Assign) or not _is_jit_call_expr(
            sub.value, jit_attrs
        ):
            continue
        for tgt in sub.targets:
            for leaf in ast.walk(tgt):
                if isinstance(leaf, ast.Name):
                    names.add(leaf.id)
    return names


def _is_jit_call_expr(expr, jit_attrs) -> bool:
    return (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Attribute)
        and isinstance(expr.func.value, ast.Name)
        and expr.func.value.id == "self"
        and expr.func.attr in jit_attrs
    )


def check_host_sync(
    files: List[SourceFile], ctx: LintContext
) -> Iterable[Finding]:
    findings: List[Finding] = []
    for sf in files:
        if sf.tree is None:
            continue
        for cls in index_classes(sf.tree):
            roots = _hot_roots(cls)
            if not roots:
                continue
            hot = reachable_set(cls, roots)
            for name in sorted(hot):
                fn = cls.methods[name].node
                traced = _jit_result_names(fn, cls.jit_attrs)
                for sub in ast.walk(fn):
                    if not isinstance(sub, ast.Call):
                        continue
                    f = sub.func
                    if isinstance(f, ast.Attribute) and f.attr in (
                        "item", "block_until_ready",
                    ):
                        findings.append(sf.finding(
                            "host-sync-hot-path", sub,
                            f".{f.attr}() in '{cls.name}.{name}' "
                            "(poll-loop-reachable) blocks the scheduler "
                            "on the device; move the read behind the "
                            "pipelined burst boundary",
                        ))
                        continue
                    if isinstance(f, ast.Attribute) and f.attr == "device_get":
                        findings.append(sf.finding(
                            "host-sync-hot-path", sub,
                            f"device_get in '{cls.name}.{name}' "
                            "(poll-loop-reachable) is a host sync",
                        ))
                        continue
                    # casts / np conversions applied to jitted results
                    target: Optional[ast.expr] = None
                    what = None
                    if (
                        isinstance(f, ast.Name)
                        and f.id in _CAST_FNS
                        and sub.args
                    ):
                        target, what = sub.args[0], f"{f.id}()"
                    elif (
                        isinstance(f, ast.Attribute)
                        and f.attr in _NP_SYNC
                        and isinstance(f.value, ast.Name)
                        and f.value.id in ("np", "numpy")
                        and sub.args
                    ):
                        target, what = sub.args[0], f"np.{f.attr}()"
                    if target is None:
                        continue
                    # metadata reads (.nbytes/.shape/.dtype/...) off a
                    # device array never touch the device: exempt names
                    # that only appear under such attributes. ``.sharding``
                    # joins the set for mesh-sharded serving — a layout
                    # read (shard_shape, is_fully_replicated) is pure
                    # metadata, same as .shape
                    meta_names = set()
                    for wrap in ast.walk(target):
                        if isinstance(wrap, ast.Attribute) and wrap.attr in (
                            "nbytes", "shape", "ndim", "size", "dtype",
                            "sharding",
                        ):
                            meta_names.update(
                                id(leaf) for leaf in ast.walk(wrap.value)
                                if isinstance(leaf, ast.Name)
                            )
                    hit = any(
                        isinstance(leaf, ast.Name)
                        and leaf.id in traced
                        and id(leaf) not in meta_names
                        for leaf in ast.walk(target)
                    )
                    if hit:
                        findings.append(sf.finding(
                            "host-sync-hot-path", sub,
                            f"{what} on a jitted-call result in "
                            f"'{cls.name}.{name}' (poll-loop-reachable) "
                            "forces an implicit device->host sync",
                        ))
    return findings


def _static_positions(call: ast.Call, statics: Tuple[int, ...]):
    for pos in statics:
        if pos < len(call.args):
            yield pos, call.args[pos]


def check_retrace(
    files: List[SourceFile], ctx: LintContext
) -> Iterable[Finding]:
    findings: List[Finding] = []
    for sf in files:
        if sf.tree is None:
            continue
        for cls in index_classes(sf.tree):
            roots = _hot_roots(cls)
            if not roots or not cls.jit_attrs:
                continue
            hot = reachable_set(cls, roots)
            for name in sorted(hot):
                fn = cls.methods[name].node
                for sub in ast.walk(fn):
                    if not isinstance(sub, ast.Call):
                        continue
                    if not _is_jit_call_expr(sub, cls.jit_attrs):
                        continue
                    attr = sub.func.attr
                    statics = cls.jit_attrs[attr]
                    for pos, arg in _static_positions(sub, statics):
                        bad = None
                        if (
                            isinstance(arg, ast.Call)
                            and isinstance(arg.func, ast.Name)
                            and arg.func.id == "len"
                        ):
                            bad = (
                                "len(...) at a static position retraces "
                                "per distinct size; pass a bucketized "
                                "value (pow2 group size, attn bucket)"
                            )
                        elif isinstance(arg, ast.Constant) and isinstance(
                            arg.value, float
                        ):
                            bad = (
                                "float constant at a static position: "
                                "continuous-domain statics re-specialize "
                                "the executable; make it a traced operand"
                            )
                        elif (
                            isinstance(arg, ast.Call)
                            and isinstance(arg.func, ast.Name)
                            and arg.func.id == "float"
                        ):
                            bad = (
                                "float(...) at a static position: "
                                "continuous-domain statics re-specialize "
                                "the executable; make it a traced operand"
                            )
                        elif isinstance(arg, (ast.Dict, ast.List, ast.Set)):
                            bad = (
                                "unhashable container literal at a static "
                                "position of a jitted callable"
                            )
                        if bad:
                            findings.append(sf.finding(
                                "retrace-hazard", arg,
                                f"self.{attr}(...) arg {pos} in "
                                f"'{cls.name}.{name}': {bad}",
                            ))
    return findings
