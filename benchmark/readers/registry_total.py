"""The program's counters as they stood when the window opened: totals since
the process began, for what set-up did. ``obs["counters"]`` is the window's
difference and the span recorder is on only inside the window, so neither
says anything of set-up; the registry (``lzy_tpu.utils.metrics.REGISTRY``)
is read as it stands after the run, and the window's own difference is
taken off where ``obs["counters"]`` has the sample (a serving cell; the
training cell builds nothing in its window, or ``correct`` is false).

A term of ``numerator``, ``denominator`` or ``subtract`` names a series of
the exposition: ``"lzy_x_total"``, or ``{"series": "lzy_x_seconds_sum",
"labels": {"stage": ["trace", "lower"]}}`` for the samples whose labels
take one of the listed values. ``sites`` / ``not_sites`` keep or drop
samples by their ``site`` label, in every term (a sample without that label
is kept). The value is ``scale * (numerator - subtract) / denominator``,
or without a denominator ``scale * (numerator - subtract)``.

None where the registry has no series of the numerator's names (a program
from before the build meter), or the denominator is 0."""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def samples(text: str) -> list:
    """``(key, series, labels, value)`` for every sample line of a
    Prometheus text exposition; ``key`` is the line less its value."""
    out = []
    for line in text.splitlines():
        m = None if line.startswith("#") else _SAMPLE.match(line)
        if m:
            out.append((line.rpartition(" ")[0], m.group(1),
                        dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def _wanted(term, series: str, labels: dict) -> bool:
    if isinstance(term, str):
        return series == term
    return series == term["series"] and all(
        labels.get(k) in allowed
        for k, allowed in term.get("labels", {}).items())


def read(obs, *, numerator, denominator=None, subtract=(), sites=None,
         not_sites=(), scale=1.0):
    from lzy_tpu.utils.metrics import REGISTRY

    rows = samples(REGISTRY.exposition())
    names = {t if isinstance(t, str) else t["series"] for t in numerator}
    if not names & {series for _, series, _, _ in rows}:
        return None
    window = obs.get("counters") or {}

    def total(terms) -> float:
        out = 0.0
        for key, series, labels, value in rows:
            site = labels.get("site")
            if site is not None and (site in not_sites or (
                    sites is not None and site not in sites)):
                continue
            if any(_wanted(t, series, labels) for t in terms):
                out += value - window.get(key, 0.0)
        return out

    value = total(numerator) - total(subtract)
    if denominator is None:
        return scale * value
    den = total(denominator)
    return scale * value / den if den else None
