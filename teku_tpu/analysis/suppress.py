"""Suppression file: every entry needs a checker, a key match, and a
real justification.

Policy (README "Static analysis"): a suppression is a debt record,
not an off switch.  An entry's `match` must EQUAL the finding's
stable `path:token` (the key minus its checker prefix — no line
numbers, so edits can't silently orphan them; no substring matching,
so an entry can never silently WIDEN to cover a new finding that
merely shares a prefix).  An entry whose justification is missing or
hand-wavy short is a HARD error: the file fails to load and lint
exits 2, because an unjustified suppression is indistinguishable from
a silenced bug.  Unused entries are reported so the file shrinks as
fixes land.
"""

import json
from typing import Dict, List, Tuple

from .findings import Finding

MIN_JUSTIFICATION = 16      # characters; "wontfix" is not a reason


class SuppressionError(ValueError):
    """The suppression file itself is invalid — a hard error, never a
    silent skip."""


def load(path: str) -> List[Dict[str, str]]:
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        return []
    except (OSError, json.JSONDecodeError) as exc:
        raise SuppressionError(f"cannot read suppression file {path}: "
                               f"{exc}")
    entries = doc.get("suppressions") if isinstance(doc, dict) else None
    if entries is None or not isinstance(entries, list):
        raise SuppressionError(
            f"{path}: expected {{\"suppressions\": [...]}}")
    for i, entry in enumerate(entries):
        if not isinstance(entry, dict):
            raise SuppressionError(f"{path}: entry {i} is not an object")
        for field in ("checker", "match", "justification"):
            value = entry.get(field)
            if not isinstance(value, str) or not value.strip():
                raise SuppressionError(
                    f"{path}: entry {i} is missing `{field}` — every "
                    "suppression needs a checker, a key match, and a "
                    "justification")
        if len(entry["justification"].strip()) < MIN_JUSTIFICATION:
            raise SuppressionError(
                f"{path}: entry {i} justification "
                f"{entry['justification']!r} is too short (< "
                f"{MIN_JUSTIFICATION} chars) — say WHY the finding is "
                "deliberate")
    return entries


def apply(findings: List[Finding], entries: List[Dict[str, str]]
          ) -> Tuple[List[Finding], List[Dict[str, str]]]:
    """Mark suppressed findings in place; return (findings, unused
    entries)."""
    used = [False] * len(entries)
    for finding in findings:
        for i, entry in enumerate(entries):
            # EXACT key equality: `checker:match` == the finding key.
            # Substring matching would let one justified entry
            # silently swallow every future finding sharing a prefix (e.g.
            # a TEKU_TPU_MESH entry absorbing TEKU_TPU_MESH_SELF_HEAL).
            if finding.key == f"{entry['checker']}:{entry['match']}":
                finding.suppressed = True
                finding.justification = entry["justification"]
                used[i] = True
                break
    unused = [entry for i, entry in enumerate(entries) if not used[i]]
    return findings, unused
