"""CL401: transfer-seam bypass (the round-9 byte-accounting contract).

Every H2D/D2H byte must flow through ``ops/device.py``'s
``xfer_put`` / ``xfer_fetch`` seam — that is what makes
``xfer.h2d_bytes`` / ``xfer.d2h_bytes`` trustworthy enough to
read as bytes on the link. A raw ``jax.device_put`` (or a
``np.asarray(...)`` host-materialization of a dispatch result)
anywhere else ships bytes the accounting never sees, and the diet
silently rots.

Flagged outside ``ops/device.py``:

- ``jax.device_put(...)`` / ``jax.device_get(...)`` — always;
- ``jax.block_until_ready(...)`` — always (legitimate
  execution-waits are baselined with that justification; a wait that
  *precedes a raw fetch* is the classic bypass shape);
- ``np.asarray(v)`` / ``v.item()`` where ``v`` was bound from a known
  device-dispatch call (a donating jit entry, ``converge_async``, or
  ``xfer_put`` itself) — a D2H fetch dressed as a cast.

Baseline fingerprints anchor on ``<op>:<enclosing function>:<ordinal
within that function>`` so they survive unrelated line churn.
"""

from __future__ import annotations

import ast
from collections import Counter
from typing import Dict, Iterable, List, Set

from tools.crdtlint.astutil import (
    assigned_names,
    call_name,
    dotted,
    enclosing_function_map,
)
from tools.crdtlint.core import Checker, Finding, LintContext, Module

SEAM_SUFFIX = "ops/device.py"
_ALWAYS_FLAGGED = ("device_put", "device_get", "block_until_ready")
# call names whose results live on device (fed by the donate index)
_DEVICE_PRODUCERS = ("converge_async", "xfer_put")
_FETCHY_CASTS = ("asarray",)  # np.asarray / _np.asarray — jnp stays on device


class TransferSeamChecker(Checker):
    name = "xfer-seam"
    codes = {
        "CL401": "H2D/D2H traffic outside the ops/device.py "
                 "xfer_put/xfer_fetch accounting seam",
    }
    explain = {
        "CL401": (
            "xfer.h2d_bytes / xfer.d2h_bytes are regression-gated; "
            "a raw device_put or an np.asarray of a dispatch result "
            "ships bytes the gate never sees, and the transfer diet "
            "silently rots.\n"
            "Fix: route uploads through xfer_put and fetches "
            "through xfer_fetch; a pure execution wait "
            "(block_until_ready with no bytes moving) is baselined "
            "with exactly that justification."
        ),
    }

    def check_module(self, mod: Module,
                     ctx: LintContext) -> Iterable[Finding]:
        if mod.path.endswith(SEAM_SUFFIX):
            return ()
        findings: List[Finding] = []
        donating: Dict[str, object] = ctx.shared.get("donating_defs", {})
        fn_of = enclosing_function_map(mod.tree)
        ordinals: Counter = Counter()

        def sym(op: str, node: ast.AST) -> str:
            fn = fn_of.get(id(node), "<module>")
            key = f"{op}:{fn}"
            ordinals[key] += 1
            return f"{key}:{ordinals[key]}"

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            tail = name.rsplit(".", 1)[-1]
            # `x.block_until_ready()` — the array-method spelling — is
            # the same wait as `jax.block_until_ready(x)`; only JAX
            # arrays grow that method, so any attribute call counts
            # (including on un-dotted receivers like `f(x).block_...`)
            method_wait = (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            )
            if method_wait and not name:
                name = tail = "block_until_ready"
            if tail in _ALWAYS_FLAGGED and (
                name.startswith("jax.") or name == tail or method_wait
            ):
                findings.append(Finding(
                    mod.path, node.lineno, "CL401",
                    f"`{name}` outside ops/device.py — route "
                    f"transfers through xfer_put/xfer_fetch so the "
                    f"round-9 byte accounting sees them "
                    f"(block_until_ready: baseline with an "
                    f"'execution wait, not transfer' justification "
                    f"if no bytes move)",
                    symbol=sym(tail, node),
                ))

        # device-value taint per function, IN SOURCE ORDER: a name is
        # tainted while bound to a dispatch result and untainted the
        # moment it is rebound from anything else (`x = xfer_fetch(x)`
        # produces a host array — a later `np.asarray(x)` is not a
        # bypass, and neither is one that textually PRECEDES the
        # dispatch that binds x)
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            tainted: Set[str] = set()
            # uses are checked before the same line's rebind takes
            # effect, so `x = np.asarray(x)` on a tainted x still fires
            events = sorted(
                (n for n in ast.walk(fn)
                 if isinstance(n, (ast.Call, ast.Assign))),
                key=lambda n: (
                    n.lineno, isinstance(n, ast.Assign), n.col_offset
                ),
            )
            for node in events:
                if isinstance(node, ast.Assign):
                    is_producer = False
                    if isinstance(node.value, ast.Call):
                        cname = (call_name(node.value) or "").rsplit(
                            ".", 1
                        )[-1]
                        # donating_defs maps name -> list of defs (one
                        # per defining module); any non-factory def
                        # means the call returns a device value
                        cands = donating.get(cname) or ()
                        is_producer = (
                            cname in _DEVICE_PRODUCERS
                            or any(
                                not getattr(d, "is_factory", True)
                                for d in cands
                            )
                        )
                    for t in node.targets:
                        if is_producer:
                            tainted.update(assigned_names(t))
                        else:
                            tainted.difference_update(
                                assigned_names(t)
                            )
                    continue
                cname = call_name(node) or ""
                tail = cname.rsplit(".", 1)[-1]
                if tail in _FETCHY_CASTS and not cname.startswith(
                    "jnp."
                ):
                    for a in node.args[:1]:
                        tgt = dotted(a)
                        if tgt in tainted:
                            findings.append(Finding(
                                mod.path, node.lineno, "CL401",
                                f"`{cname}({tgt})` host-materializes "
                                f"a device dispatch result outside "
                                f"the seam — use xfer_fetch so the "
                                f"D2H bytes are accounted",
                                symbol=f"asarray:{fn.name}:{tgt}",
                            ))
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"
                        and not node.args):
                    base = dotted(node.func.value)
                    if base in tainted:
                        findings.append(Finding(
                            mod.path, node.lineno, "CL401",
                            f"`.item()` on device value `{base}` "
                            f"outside the seam — a hidden D2H "
                            f"transfer; fetch through xfer_fetch",
                            symbol=f"item:{fn.name}:{base}",
                        ))
        return findings
