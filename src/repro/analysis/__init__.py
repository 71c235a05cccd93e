"""Static layout/access-pattern linter for the MARS repro.

Three pass families, one findings model, one CLI
(``python -m repro.analysis``):

* ``access`` — pack-width alignment of the bit-plane codec and the
  ``DATA_TYPES`` container widths (ACC103);
* ``obs_discipline`` — AST proof that no ``repro.obs`` recording call
  is reachable inside a traced function (OBS201);
* ``layout_invariants`` — solved layouts over the config zoo are valid
  permutations with honest burst accounting (LAY301/LAY302), MARS
  partitions hold (LAY303), codec bit format stays in bounds (LAY304).

Findings gate via a fingerprint suppression baseline
(``baseline.json``, kept empty) and publish as ``analysis/*`` obs
series.  See ``README.md`` in this package for the rule catalog.
"""
from .findings import Finding, SEVERITIES  # noqa: F401
