"""How the fixed paragraph list of ``batch.paragraph.json`` was drawn.

    python3 perfbench/traffic/draw_paragraphs.py

prints the list.  It is kept so that the list can be checked against its
description (``drawn`` in the traffic file); no run reads it.  Sentence
lengths are IPA characters, log-normal after LJ Speech's published clip
statistics, truncated to what the stock path runs in two frame buckets;
the longest sentence of a paragraph decides its frame bucket, so 16
paragraphs are drawn with their longest sentence at 80, 81, ... 95
characters (the live zone of the frame-budget estimator) and 48 under it.
"""

import json
import math
import random

MEAN, SIGMA, SHORTEST = 70.7, 0.35, 32
LIVE, LOW = range(80, 96), (71, 79)
PARAGRAPHS, SENTENCES, SEED = 64, 8, 24


def draw() -> list:
    rng = random.Random(SEED)
    mu = math.log(MEAN) - 0.5 * SIGMA ** 2

    def sentence(cap: int) -> int:
        while True:
            c = int(round(math.exp(rng.gauss(mu, SIGMA))))
            if SHORTEST <= c <= cap:
                return c

    out = []
    for longest in LIVE:
        while True:
            p = [sentence(longest) for _ in range(SENTENCES)]
            if max(p) == longest:
                break
        out.append(p)
    while len(out) < PARAGRAPHS:
        p = [sentence(LOW[1]) for _ in range(SENTENCES)]
        if max(p) >= LOW[0]:
            out.append(p)
    rng.shuffle(out)
    return out


if __name__ == "__main__":
    print(json.dumps(draw()))
