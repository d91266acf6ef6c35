"""Independent tashkeel quality eval (VERDICT r2 next#7).

The bundled default tagger was trained to reproduce the repo's own rule
engine (tools/train_tashkeel.py), so agreement-with-rules says nothing
about Arabic quality.  This script measures both the rule engine and the
bundled tagger against a hand-curated gold corpus of fully-vocalized MSA
sentences (tools/tashkeel_gold.txt — typed in, no external assets), and
writes ``TASHKEEL_EVAL.json`` at the repo root.

Metrics (standard diacritization eval, libtashkeel's own framing):

- **DER** (diacritic error rate): fraction of Arabic base letters whose
  predicted diacritic string differs from gold.  Counted with and without
  case endings.
- **case-ending accuracy**: last Arabic letter of each word only — the
  hardest part (iʿrāb) and what an eval against the rule engine can never
  measure honestly.

Run: ``python tools/eval_tashkeel.py`` (CPU is fine; the tagger is tiny).
"""

from __future__ import annotations

import json
import os
import sys
import unicodedata
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# the tagger is tiny — always run this eval on CPU (set before the first
# jax import, which is when JAX reads it)
os.environ["JAX_PLATFORMS"] = "cpu"

HARAKAT = set("ًٌٍَُِّْٰ")


def split_letters(text: str) -> list[tuple[str, str]]:
    """[(base letter, attached diacritic string)] for Arabic letters."""
    out: list[tuple[str, str]] = []
    for ch in text:
        if ch in HARAKAT:
            if out:
                base, marks = out[-1]
                # normalized order: shadda first, then the vowel
                out[-1] = (base, "".join(sorted(marks + ch,
                                                key=lambda c: c != "ّ")))
        elif unicodedata.category(ch).startswith("L"):
            out.append((ch, ""))
        else:
            out.append((ch, ""))  # punctuation/space: alignment anchor
    return out


def word_spans(letters: list[tuple[str, str]]) -> list[tuple[int, int]]:
    spans, start = [], None
    for i, (base, _m) in enumerate(letters):
        is_arabic = "؀" <= base <= "ۿ"
        if is_arabic and start is None:
            start = i
        elif not is_arabic and start is not None:
            spans.append((start, i))
            start = None
    if start is not None:
        spans.append((start, len(letters)))
    return spans


def score(pred: str, gold: str) -> dict:
    pl, gl = split_letters(pred), split_letters(gold)
    if [b for b, _ in pl] != [b for b, _ in gl]:
        raise ValueError("base-letter skeletons diverge:\n"
                         f"  pred: {pred}\n  gold: {gold}")
    spans = word_spans(gl)
    finals = {hi - 1 for _lo, hi in spans}
    stats = {"letters": 0, "errors": 0, "letters_no_ce": 0,
             "errors_no_ce": 0, "finals": 0, "final_errors": 0}
    for i, ((_b, pm), (_b2, gm)) in enumerate(zip(pl, gl)):
        if not ("؀" <= _b <= "ۿ"):
            continue
        stats["letters"] += 1
        err = pm != gm
        stats["errors"] += err
        if i in finals:
            stats["finals"] += 1
            stats["final_errors"] += err
        else:
            stats["letters_no_ce"] += 1
            stats["errors_no_ce"] += err
    return stats


def accumulate(total: dict, s: dict) -> None:
    for k, v in s.items():
        total[k] = total.get(k, 0) + v


def main() -> int:
    from sonata_tpu.models.tashkeel import TashkeelModel, strip_diacritics
    from sonata_tpu.text import tashkeel_rules

    gold_lines = [ln.strip() for ln in
                  (REPO / "tools" / "tashkeel_gold.txt").read_text(
                      encoding="utf-8").splitlines() if ln.strip()]

    systems = {"rules": tashkeel_rules.diacritize}
    bundled = REPO / "sonata_tpu" / "data" / "tashkeel_default.npz"
    if bundled.exists():
        model = TashkeelModel.from_path(bundled)
        systems["bundled_tagger"] = model.diacritize

    report = {"corpus": "tools/tashkeel_gold.txt",
              "sentences": len(gold_lines), "systems": {}}
    for name, fn in systems.items():
        totals: dict = {}
        for gold in gold_lines:
            bare = strip_diacritics(gold)
            accumulate(totals, score(fn(bare), gold))
        report["systems"][name] = {
            "der": round(totals["errors"] / totals["letters"], 4),
            "der_no_case_endings": round(
                totals["errors_no_ce"] / totals["letters_no_ce"], 4),
            "case_ending_accuracy": round(
                1 - totals["final_errors"] / totals["finals"], 4),
            "letters": totals["letters"],
            "words": totals["finals"],
        }
    out = REPO / "TASHKEEL_EVAL.json"
    out.write_text(json.dumps(report, indent=2, ensure_ascii=False) + "\n",
                   encoding="utf-8")
    print(json.dumps(report, indent=2, ensure_ascii=False))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
