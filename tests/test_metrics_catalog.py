"""Metric-catalog drift gate (ISSUE 8 satellite): the
docs/OBSERVABILITY.md catalog table and the process-global metric
registry can never drift apart again.

Direction 1: every ``/stf/...`` family registered when the library (and
the model-zoo gate's graph builders) are imported must have a catalog
row. Direction 2: every catalog row must name a family that actually
registers. ``docs/observability_allowlist.txt`` exempts names in both
directions — intentionally, loudly, one per line.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")
ALLOWLIST = os.path.join(REPO, "docs", "observability_allowlist.txt")


def _registered_names():
    # the root import registers every metric-bearing module (session,
    # optimizer, analysis, data.pipeline, serving, telemetry); the zoo
    # modules ride along for any graph-time registrations
    import simple_tensorflow_tpu  # noqa: F401
    import simple_tensorflow_tpu.models  # noqa: F401
    # not imported by the package: without it this file passes only in
    # a worker that an earlier test file already made import it
    import simple_tensorflow_tpu.models.sparse_moe_lm  # noqa: F401
    import simple_tensorflow_tpu.models.latent_moe_lm  # noqa: F401
    import simple_tensorflow_tpu.models.state_space_moe_lm  # noqa: F401
    from simple_tensorflow_tpu.platform import monitoring

    return {n for n in monitoring._registry if n.startswith("/stf/")}


def _documented_names():
    with open(DOC) as f:
        text = f.read()
    # catalog rows are markdown table rows whose first cell is the
    # backticked metric name
    return set(re.findall(r"^\|\s*`(/stf/[^`]+)`", text, re.MULTILINE))


def _allowlisted():
    names = set()
    with open(ALLOWLIST) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if line:
                names.add(line)
    return names


def test_catalog_parses_nonempty():
    docs = _documented_names()
    assert len(docs) > 30, (
        "docs/OBSERVABILITY.md catalog table parse came back "
        f"suspiciously small ({len(docs)} rows) — did the table format "
        "change? Update the regex in this test alongside it.")


def test_every_registered_metric_is_documented():
    missing = _registered_names() - _documented_names() - _allowlisted()
    assert not missing, (
        "metric families registered at import but MISSING from the "
        "docs/OBSERVABILITY.md catalog table (add a row, or — only for "
        "intentional omissions — an allowlist line):\n  "
        + "\n  ".join(sorted(missing)))


def test_every_documented_metric_is_registered():
    ghosts = _documented_names() - _registered_names() - _allowlisted()
    assert not ghosts, (
        "docs/OBSERVABILITY.md catalog rows that no longer correspond "
        "to a registered metric family (stale docs rot trust — delete "
        "the row or fix the registration):\n  "
        + "\n  ".join(sorted(ghosts)))


def test_allowlist_entries_are_live():
    # an allowlist line for a name that neither registers nor appears
    # in the docs is dead weight — fail so it gets cleaned up
    dead = [n for n in _allowlisted()
            if n not in _registered_names()
            and n not in _documented_names()]
    assert not dead, (
        "docs/observability_allowlist.txt entries matching nothing: "
        f"{sorted(dead)}")


def test_allowlist_is_not_growing_silently():
    # the steady state is an EMPTY allowlist; this bound forces a
    # deliberate edit (and review) to grow it past a handful
    n = len(_allowlisted())
    assert n <= 5, (
        f"observability allowlist has {n} entries — it is meant for "
        "rare, temporary exemptions, not as a pressure valve. Document "
        "the metrics instead.")


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
