"""Flag-hygiene pass.

Closes the loop on the ``FLAGS_*`` registry three ways:

- **read-unregistered**: a ``"FLAGS_x"`` string anywhere in the code
  that does not resolve to a key of ``_FLAGS`` in
  ``framework/flags.py`` is a typo or a missing registration — the read
  would silently fall back to its call-site default forever.
- **registered-unread**: a registered flag no code ever reads is dead
  weight (or its consumer was deleted). Reference-compatibility flags
  that are accepted-but-inert by design are pinned in ``INERT`` with the
  reason; anything else must have a reader.
- **undocumented**: every registered flag needs a row in a docs flags
  table (``docs/*.md`` or ``README.md``) — a knob nobody can discover
  is a knob nobody tunes.

The pass is string-literal based by design: flags are read through
``get_flag("FLAGS_x", ...)`` / env overrides, so the literal *is* the
reference. Occurrences inside ``framework/flags.py`` itself do not
count as reads.
"""
from __future__ import annotations

import ast
import os
import re

from ..core import Finding, register_pass, waived

FLAGS_FILE = "paddle_tpu/framework/flags.py"
CODE_SCAN = ["paddle_tpu", "tests", "tools", "bench.py"]
DOCS_SCAN = ["docs", "README.md"]

# Flags registered for script compatibility with the reference project:
# accepted (and env-overridable) so existing launch scripts do not error,
# but deliberately inert on this backend. Exempt from registered-unread;
# still required to be documented.
INERT = [
    "FLAGS_fraction_of_gpu_memory_to_use",   # no GPU allocator here
    "FLAGS_allocator_strategy",              # jax owns device memory
    "FLAGS_use_standalone_executor",         # single executor path
    "FLAGS_deterministic",                   # XLA is deterministic by
                                             # default; nothing to gate
    "FLAGS_cudnn_deterministic",             # cudnn parity alias of the
                                             # above; no cudnn here
    "FLAGS_log_level",                       # reference tracer-verbosity
                                             # knob; our tracer has no
                                             # log levels (yet)
]

_FLAG_RE = re.compile(r"\bFLAGS_[A-Za-z0-9_]+\b")
_WAIVE = "flag-ok"


def _registered(ctx):
    """{flag: lineno} parsed from the _FLAGS dict literal."""
    sf = ctx.source(FLAGS_FILE)
    if sf is None:
        return {}
    out = {}
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.AnnAssign) \
                and getattr(node.target, "id", None) == "_FLAGS" \
                and isinstance(node.value, ast.Dict):
            for key in node.value.keys:
                if isinstance(key, ast.Constant) \
                        and isinstance(key.value, str):
                    out[key.value] = key.lineno
    return out


@register_pass
class FlagHygienePass:
    name = "flag-hygiene"
    description = ("every FLAGS_* read is registered + documented; every "
                   "registered flag is read")
    version = "1"
    scan = CODE_SCAN
    scan_docs = DOCS_SCAN       # .md inputs fold into the cache key
    file_local = False          # reads/registry join is cross-file

    def run(self, ctx):
        findings = []
        registered = _registered(ctx)
        if not registered:
            return [Finding(
                self.name, FLAGS_FILE, 1, "no-registry",
                "could not parse the _FLAGS dict literal out of "
                f"{FLAGS_FILE}", symbol="_FLAGS")]

        # -- reads: every string literal mentioning a flag ---------------------
        # A trailing-underscore token ("FLAGS_retry_" + name) is a dynamic
        # prefix build, not a mint — skipped, like the metric pass skips
        # bare-variable names. The analysis package itself only talks
        # ABOUT flags, so it is excluded from the read scan.
        reads = {}   # flag -> first (rel, line)
        for rel in ctx.py_files(CODE_SCAN):
            if rel == FLAGS_FILE \
                    or rel.startswith("paddle_tpu/analysis/"):
                continue
            sf = ctx.source(rel)
            if sf is None:
                continue
            try:
                tree = sf.tree
            except SyntaxError:
                continue  # blocking/typed passes already report these
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    for flag in _FLAG_RE.findall(node.value):
                        if flag.endswith("_"):
                            continue  # dynamic prefix build, not a read
                        reads.setdefault(flag, (rel, node.lineno))
                        if flag not in registered:
                            if waived(sf, node.lineno, _WAIVE):
                                continue
                            findings.append(Finding(
                                self.name, rel, node.lineno,
                                "read-unregistered",
                                f"'{flag}' is not registered in "
                                f"{FLAGS_FILE} — typo, or add it to "
                                "_FLAGS (and the docs flags table)",
                                symbol=flag))

        # -- docs coverage ----------------------------------------------------
        documented = set()
        for rel in _doc_files(ctx):
            sf = ctx.source(rel)
            if sf is None:
                continue
            documented.update(_FLAG_RE.findall(sf.text))

        inert = set(INERT)
        for flag, lineno in sorted(registered.items()):
            if flag not in documented:
                findings.append(Finding(
                    self.name, FLAGS_FILE, lineno, "undocumented",
                    f"'{flag}' is registered but appears in no docs "
                    "flags table (docs/*.md or README.md)",
                    symbol=flag))
            if flag not in reads and flag not in inert:
                findings.append(Finding(
                    self.name, FLAGS_FILE, lineno, "registered-unread",
                    f"'{flag}' is registered but never read outside "
                    f"{FLAGS_FILE} — wire a consumer, remove it, or pin "
                    "it in the pass's INERT list with the reason",
                    symbol=flag))
        for flag in sorted(inert):
            if flag not in registered:
                findings.append(Finding(
                    self.name, FLAGS_FILE, 1, "stale-inert",
                    f"INERT pins '{flag}' but it is no longer "
                    "registered — drop the pin", symbol=flag))
        return findings


def _doc_files(ctx):
    out = []
    for entry in DOCS_SCAN:
        path = os.path.join(ctx.root, entry)
        if os.path.isfile(path):
            out.append(entry)
        elif os.path.isdir(path):
            for fn in sorted(os.listdir(path)):
                if fn.endswith(".md"):
                    out.append(f"{entry}/{fn}")
    for rel in ctx.overlay:
        if rel.endswith(".md") and rel not in out:
            if any(rel == e or rel.startswith(e.rstrip('/') + "/")
                   for e in DOCS_SCAN):
                out.append(rel)
    return out
