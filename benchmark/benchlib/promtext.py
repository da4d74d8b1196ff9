"""The benchmark's own reading of a Prometheus text page and of the
differences between two of them (the server's `/metrics`)."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict[tuple[str, tuple[tuple[str, str], ...]], float]:
    """{(sample name, sorted label pairs): value}; comments are skipped."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(3) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(4))
        except ValueError:
            continue
    return out


def total(samples: dict, name: str, **want: str) -> float:
    """Sum of every sample of `name` whose labels include all of `want`."""
    return sum(
        v for (n, labels), v in samples.items()
        if n == name and all(dict(labels).get(k) == w for k, w in want.items())
    )


def delta(before: dict, after: dict, name: str, **want: str) -> float:
    """Growth of the matching samples between two pages. A counter that the
    first page did not have yet started at 0."""
    return total(after, name, **want) - total(before, name, **want)


def by_label(before: dict, after: dict, name: str, label: str) -> dict[str, float]:
    """{label value: growth} of one family, split by one of its labels."""
    out: dict[str, float] = {}
    for (n, labels), v in after.items():
        if n == name:
            key = dict(labels).get(label, "")
            out[key] = out.get(key, 0.0) + v - before.get((n, labels), 0.0)
    return out


def interpolate(a: dict, b: dict, w: float) -> dict:
    """The page as it stood between two pages, a at w = 0 and b at w = 1,
    every sample moved along a straight line."""
    w = min(1.0, max(0.0, w))
    return {k: a.get(k, 0.0) + w * (b.get(k, a.get(k, 0.0)) - a.get(k, 0.0))
            for k in set(a) | set(b)}


def grown_by_two(before: dict, after: dict, name: str, outer: str, inner: str
                 ) -> dict[str, dict[str, float]]:
    """{outer label value: {inner label value: growth}} of one sample name,
    as the busy and wait seconds of each stage of a pipeline."""
    out: dict[str, dict[str, float]] = {}
    for (n, labels), value in after.items():
        if n == name:
            lab = dict(labels)
            out.setdefault(lab.get(outer, ""), {})[lab.get(inner, "")] = (
                value - before.get((n, labels), 0.0))
    return out
