"""Text for the traffic, and the plain text -> phoneme-id path.

A traffic file fixes the multiset of sentence lengths; the seed chooses only
the words and the order.  A sentence of length ``n`` is lower-case words
from the word list joined by single spaces and closed by a period, such
that its IPA form has exactly ``n`` characters (the period included), so
its phoneme-id row has exactly ``2 * n + 2`` ids whatever the seed.

``text_to_ids`` is the reference's own text stage: look each word up in the
word list, join with spaces, add the period, interleave the pad id
(Piper's encoding: bos, then id + pad per symbol, then eos).  It imports
nothing of the program; the word list is a golden table.
"""

from __future__ import annotations

import random
from pathlib import Path


class Lexicon:
    def __init__(self, path):
        self.ipa: dict = {}
        for line in Path(path).read_text(encoding="utf-8").splitlines():
            if not line or line.startswith("#"):
                continue
            word, ipa = line.split("\t")
            self.ipa[word] = ipa
        self.by_len: dict = {}
        for word in sorted(self.ipa):
            self.by_len.setdefault(len(self.ipa[word]), []).append(word)
        self.words = sorted(self.ipa)
        self.lengths = sorted(self.by_len)

    def sentence(self, n: int, rng: random.Random) -> str:
        """Words whose IPA, spaces and final period come to ``n`` chars."""
        lo, hi = self.lengths[0], self.lengths[-1]
        left = n - 1  # the period
        words = []
        while left > hi:
            # leave room for a last word: after this word and a space at
            # least ``lo`` characters remain
            word = rng.choice(self.words)
            if left - len(self.ipa[word]) - 1 < lo:
                continue
            words.append(word)
            left -= len(self.ipa[word]) + 1
        if left not in self.by_len:
            raise ValueError(f"no word of IPA length {left} closes a "
                             f"sentence of {n}")
        words.append(rng.choice(self.by_len[left]))
        return " ".join(words) + "."

    def sentence_ipa(self, sentence: str) -> str:
        body = sentence.rstrip(".")
        return " ".join(self.ipa[w] for w in body.split(" ")) + "."


def paragraph_text(lexicon: Lexicon, lengths, rng: random.Random) -> list:
    return [lexicon.sentence(n, rng) for n in lengths]


def text_to_ids(lexicon: Lexicon, sentence: str, id_map: dict) -> list:
    pad = id_map["_"][0]
    ids = [id_map["^"][0]]
    for ch in lexicon.sentence_ipa(sentence):
        ids += [id_map[ch][0], pad]
    ids.append(id_map["$"][0])
    return ids


def schedule(traffic: dict, seed: int) -> list:
    """The order in which the paragraphs of the list are sent: every pass
    over the list is a fresh permutation drawn from the seed, so every
    seed sends the same multiset in another order."""
    rng = random.Random(int(seed) * 1000003 + 17)
    n = len(traffic["paragraphs"])
    order = []
    for _ in range(int(traffic.get("passes", 64))):
        perm = list(range(n))
        rng.shuffle(perm)
        order += perm
    return order
