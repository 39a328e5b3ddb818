"""Donation-taint pass: the write-seam contract for tensor backing state.

The PR 10 donation contract (docs/compiled_step.md) hangs off three
attributes of :class:`~paddle_tpu.core.tensor.Tensor`:

- ``_val``            — the raw jax backing array. Writing it bypasses the
  ``_value`` property (trace hooks + taint) entirely; a buffer swapped in
  this way can alias external state, and donating it corrupts that state
  silently (the exact memory-corruption class the compiled step's donation
  gate exists to prevent).
- ``_donate_unsafe``  — the taint bit the donation gate reads
  (``jit/to_static.py::_donation_gate``). While it is set the gate donates
  the value as it stands only where it can see that the value is neither
  on the CPU (where it may be a numpy buffer PJRT merely imported) nor
  held by anything but its tensor, and donates a device copy otherwise.
  Clearing it anywhere but a contracted write-back seam re-arms plain
  donation on a buffer whose aliasing the seam never proved.
- ``_degen_cache``    — the degenerate-dim cache (ops/_param_guard.py).
  Re-initializing a value without invalidating it serves stale geometry
  (the ADVICE r5 ``set_state_dict`` bug class).

So: **every write to a contracted attribute must happen inside a
registered write seam** — a function whose ``def`` line carries a

    def _run(self, prog, args, kwargs):   # write-seam: <why this is safe>

annotation (line above also accepted). The annotation is the
registration; the ``SEEDED`` manifest below pins the contracted core
seams so deleting an annotation is itself a finding (``unseeded``), and
a seam that vanishes outright is ``stale-seam``. ``__init__``/``__new__``
bodies are exempt for ``self.*`` writes only (the object is not shared
yet); nested defs need their own annotation (closures escape into traces
and worker threads).

The pass also hard-checks the seam contract itself (``seam-contract``):
the ``Tensor._value`` property setter must keep setting
``_donate_unsafe`` — that setter being a taint source is what makes
every ordinary ``t._value = v`` assignment safe.

Waive a single reviewed line inline::

    t._val = v   # taint-ok: throwaway probe tensor, never donated
"""
from __future__ import annotations

import ast

from ..core import Finding, register_pass, waived

SCAN = ["paddle_tpu"]

# Attributes whose writes are contracted to registered seams.
CONTRACTED = ("_val", "_donate_unsafe", "_degen_cache")

_ANNOTATION = "write-seam:"
_WAIVE = "taint-ok"

# Contracted core seams: these (rel, qualname) functions carry the
# donation/taint machinery itself and MUST stay annotated — a PR that
# strips the annotation (with or without keeping the writes) fails.
SEEDED = [
    ("paddle_tpu/core/tensor.py", "Tensor._value"),
    ("paddle_tpu/core/tensor.py", "Tensor.set_value"),
    ("paddle_tpu/core/tensor.py", "Tensor._replace_value"),
    ("paddle_tpu/jit/to_static.py", "StaticFunction._run"),
    ("paddle_tpu/serving/decode/kv_cache.py", "KVBlockPool.release"),
]


def _qualnames(tree):
    """Yield (dotted qualname, FunctionDef) for every def, including
    nested ones (``Cls.meth.inner``)."""
    out = []

    def walk(node, prefix):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{sub.name}"
                out.append((qual, sub))
                walk(sub, f"{qual}.")
            elif isinstance(sub, ast.ClassDef):
                walk(sub, f"{prefix}{sub.name}.")
            else:
                walk(sub, prefix)

    walk(tree, "")
    return out


def _is_seam(sf, fn):
    """Annotated on the def line or in the contiguous comment block
    directly above it (multi-line lead comments are one registration)."""
    if _ANNOTATION in sf.comment_on(fn.lineno):
        return True
    line = fn.lineno - 1
    while line > 0 and sf.comment_on(line):
        if _ANNOTATION in sf.comment_on(line):
            return True
        line -= 1
    return False


def _own_statements(fn):
    """The function's own body statements, excluding nested defs (which
    register — or fail to register — as their own seams)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _contracted_writes(fn):
    """Yield (node, attr, receiver-is-self) for contracted-attribute
    writes lexically in `fn` (nested defs excluded)."""
    for node in _own_statements(fn):
        targets = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            for t in ast.walk(tgt):
                if isinstance(t, ast.Attribute) and t.attr in CONTRACTED \
                        and isinstance(t.ctx, ast.Store):
                    is_self = isinstance(t.value, ast.Name) \
                        and t.value.id == "self"
                    yield node, t.attr, is_self
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "setattr" and len(node.args) >= 2 \
                and isinstance(node.args[1], ast.Constant) \
                and node.args[1].value in CONTRACTED:
            yield node, node.args[1].value, False


def _module_writes(tree, quals):
    """Contracted writes at module level (outside any def)."""
    covered = set()
    for _, fn in quals:
        for sub in ast.walk(fn):
            covered.add(id(sub))
    for node in ast.walk(tree):
        if id(node) in covered:
            continue
        targets = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            for t in ast.walk(tgt):
                if isinstance(t, ast.Attribute) and t.attr in CONTRACTED \
                        and isinstance(t.ctx, ast.Store):
                    yield node, t.attr


@register_pass
class DonationTaintPass:
    name = "donation-taint"
    description = ("writes to Tensor._val/_donate_unsafe/_degen_cache "
                   "only inside registered '# write-seam:' functions")
    version = "1"
    scan = SCAN
    file_local = True

    def run(self, ctx):
        findings = []
        seeded = {}
        for rel, qual in SEEDED:
            seeded.setdefault(rel, set()).add(qual)

        for rel in ctx.py_files(SCAN):
            if rel.startswith("paddle_tpu/analysis/"):
                continue  # the framework talks ABOUT the attrs, by name
            sf = ctx.source(rel)
            if sf is None:
                continue
            try:
                tree = sf.tree
            except SyntaxError as e:
                findings.append(Finding(
                    self.name, rel, getattr(e, "lineno", 1) or 1,
                    "unparseable", f"unparseable ({e})", symbol=rel))
                continue
            if not any(a in sf.text for a in CONTRACTED):
                continue
            quals = _qualnames(tree)
            by_qual = dict(quals)

            # -- seeded-seam guards --------------------------------------------
            for qual in sorted(seeded.get(rel, ())):
                fn = by_qual.get(qual)
                if fn is None:
                    findings.append(Finding(
                        self.name, rel, 1, "stale-seam",
                        f"contracted write seam {qual} no longer exists "
                        "in this file — update SEEDED in "
                        "passes/donation_taint.py with the successor seam",
                        symbol=qual))
                elif not _is_seam(sf, fn):
                    findings.append(Finding(
                        self.name, rel, fn.lineno, "unseeded",
                        f"{qual} is a contracted write seam but lost its "
                        f"'# {_ANNOTATION}' annotation — the donation/taint "
                        "contract is no longer registered here",
                        symbol=qual))

            # -- the seam contract itself --------------------------------------
            if rel == "paddle_tpu/core/tensor.py":
                findings.extend(self._check_setter_contract(sf, tree))

            # -- direct writes -------------------------------------------------
            for qual, fn in quals:
                leaf = qual.rsplit(".", 1)[-1]
                if _is_seam(sf, fn):
                    continue
                init_exempt = leaf in ("__init__", "__new__")
                for node, attr, is_self in _contracted_writes(fn):
                    if init_exempt and is_self:
                        continue
                    if waived(sf, node.lineno, _WAIVE):
                        continue
                    findings.append(Finding(
                        self.name, rel, node.lineno, "direct-write",
                        f"direct write to contracted attribute '{attr}' "
                        f"in {qual}, which is not a registered write seam "
                        f"— go through the Tensor._value setter / a seam "
                        f"method, or annotate the def '# {_ANNOTATION} "
                        "<why>' after review (docs/static_analysis.md)",
                        symbol=f"{attr}@{qual}"))
            for node, attr in _module_writes(tree, quals):
                if waived(sf, node.lineno, _WAIVE):
                    continue
                findings.append(Finding(
                    self.name, rel, node.lineno, "direct-write",
                    f"module-level direct write to contracted attribute "
                    f"'{attr}' — wrap it in a registered write seam",
                    symbol=f"{attr}@{rel}:module"))
        return findings

    def _check_setter_contract(self, sf, tree):
        """Tensor's ``_value`` property setter must keep setting
        ``_donate_unsafe`` — that is what makes property writes safe."""
        for node in ast.walk(tree):
            if not (isinstance(node, ast.ClassDef) and node.name == "Tensor"):
                continue
            for fn in node.body:
                if not isinstance(fn, ast.FunctionDef) \
                        or fn.name != "_value":
                    continue
                if not any(isinstance(d, ast.Attribute)
                           and d.attr == "setter"
                           for d in fn.decorator_list):
                    continue
                taints = any(
                    isinstance(sub, ast.Attribute)
                    and sub.attr == "_donate_unsafe"
                    and isinstance(sub.ctx, ast.Store)
                    for sub in ast.walk(fn))
                if not taints:
                    return [Finding(
                        self.name, sf.rel, fn.lineno, "seam-contract",
                        "the Tensor._value property setter no longer sets "
                        "_donate_unsafe — every property write in the tree "
                        "just lost its taint, and the donation gate can "
                        "donate aliased buffers (docs/compiled_step.md)",
                        symbol="Tensor._value.setter")]
                return []
        return []
