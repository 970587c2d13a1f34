"""Clock-discipline pass: the injectable-clock invariant (PR 12).

PR 12 threaded ``utils/clock.py`` (``SystemClock``/``VirtualClock``)
through the serving stack so an hour of traffic replays in seconds and
every latency-bearing test is deterministic.  That invariant regresses
silently: one new ``time.sleep()`` in a component the load plane drives
and the virtual clock stalls at its real-time backstop.  This pass
forbids raw ``time.time`` / ``time.monotonic`` / ``time.sleep`` (and
their ``_ns``/``perf_counter`` variants) everywhere in ``lzy_tpu``
except:

- ``utils/clock.py`` itself (the one legitimate consumer);
- the :data:`ALLOWLIST` below — each entry carries the justification
  the rule demands (wall time is *correct* there, not an accident);
- lines carrying a justified inline
  ``# lzy-lint: disable=clock-raw-time -- <why>``.

Components with injectable state take ``clock=None`` defaulting to
``SYSTEM_CLOCK``; free functions call the ``SYSTEM_CLOCK`` module
singleton directly — both satisfy this rule (the rule polices the
``time`` module, not which clock object you read).
"""

from __future__ import annotations

import ast
from typing import Dict, List

from lzy_tpu.analysis.core import ProjectIndex, Violation, dotted

#: forbidden attributes of the ``time`` module
_FORBIDDEN = {"time", "monotonic", "sleep", "monotonic_ns", "time_ns",
              "perf_counter", "perf_counter_ns"}

#: path -> justification. Every entry is a place where WALL time is the
#: semantically correct clock (or the module is the clock machinery
#: itself), reviewed when this pass landed. Adding an entry is a
#: reviewed decision exactly like an inline suppression.
ALLOWLIST: Dict[str, str] = {
    "lzy_tpu/utils/clock.py":
        "the clock implementation itself: SystemClock wraps time.*, and "
        "VirtualClock's real-time backstop/stall-limit polls are "
        "deliberately wall-clock (they detect participants stuck "
        "OUTSIDE the virtual clock)",
    "lzy_tpu/utils/trace.py":
        "the span recorder's stamps are wall time by contract: they are "
        "compared with Request's stamps under the system clock and tied "
        "to the profiler's clock by an anchor that embeds "
        "time.monotonic_ns(); a virtual clock would place spans nowhere "
        "on a device trace (callers stamp through trace.now())",
    "lzy_tpu/utils/ids.py":
        "wall-clock millis embedded in generated ids for sortability/"
        "debuggability — id entropy, never scheduling; a virtual clock "
        "here would collide ids across simulated runs",
    "lzy_tpu/chaos/faults.py":
        "injected delay/slow faults simulate a real dependency stall: "
        "the whole point is to burn wall time at the boundary; the "
        "chaos soaks run on the system clock by design",
    "lzy_tpu/durable/pg_store.py":
        "retry backoff against a real out-of-process Postgres; wall "
        "time is the only clock the database shares with us",
    "lzy_tpu/load/driver.py":
        "the load harness DRIVES a VirtualClock and reports how many "
        "virtual hours one wall second buys (lzy_load_speedup) — the "
        "speedup denominator and the thread-startup registration poll "
        "must read real time, never the clock under test",
}


def run(index: ProjectIndex) -> List[Violation]:
    out: List[Violation] = []
    for mod in index:
        if mod.path in ALLOWLIST:
            continue
        # alias map: `import time`, `import time as t`
        aliases = {"time"}
        from_imports: List[ast.ImportFrom] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time":
                        aliases.add(a.asname or "time")
            elif isinstance(node, ast.ImportFrom):
                if node.module == "time" and node.level == 0:
                    from_imports.append(node)
        for node in from_imports:
            names = sorted({a.name for a in node.names
                            if a.name in _FORBIDDEN})
            if names:
                out.append(Violation(
                    "clock-raw-time", mod.path, node.lineno,
                    f"`from time import {', '.join(names)}` — use the "
                    f"injectable Clock (utils/clock.py) or add a "
                    f"justified allowlist entry"))
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if not name or "." not in name:
                continue
            head, leaf = name.rsplit(".", 1)
            if head in aliases and leaf in _FORBIDDEN:
                out.append(Violation(
                    "clock-raw-time", mod.path, node.lineno,
                    f"raw {name}() — thread a Clock (clock.now()/"
                    f".time()/.sleep()) or justify an allowlist/"
                    f"suppression entry"))
    return out
