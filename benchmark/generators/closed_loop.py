"""Closed-loop editing (crdt-benchmarks B4 shape: caret-local typing and
backspace, plus Peritext formatting): every doc has one writer, and each
writer sends its next change as soon as the previous one is answered, as a
Yjs- or Automerge-style sync provider does.

Parameters (``traffic/<mix>.json``): ``changes_per_writer``, the length of
each writer's script; ``changes``, each kind's share and size range;
``mark_types``, taken in turn; ``formatted_spans`` per genesis;
``jump_share``, the chance that a writer moves its caret to a random place
before a change.  Traffic: per doc a genesis and a :class:`Script`.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import gen


class Script:
    """One writer's changes, in order.  The kinds and sizes are fixed when
    the script is made (the mix's exact shares, in a seeded order), and so
    are its random draws; each change is built from them when the script
    is first read that far, so reading it lazily or at once gives the same
    changes, and a read costs no draw."""

    def __init__(self, writer: gen.Writer, plan: List[Tuple[str, int]],
                 mix: Dict[str, Any], seed: int, doc: int) -> None:
        r = gen.rng_for(seed, 5, doc)
        m = len(plan)
        self._w = writer
        self._plan = plan
        self._jump = r.random(m) < float(mix["jump_share"])
        self._where = r.random(m)  # the caret after a jump, as a share of the text
        self._chars = r.integers(len(gen.LETTERS), size=(m, max(
            (size for kind, size in plan if kind == "typing"), default=1)))
        self._url = r.integers(len(gen.URLS), size=m)
        self._types = mix["mark_types"]
        self._doc = doc
        self._marks = 0
        self.changes: List[gen.Change] = []

    def __len__(self) -> int:
        return len(self._plan)

    def get(self, i: int) -> gen.Change:
        """Change ``i`` (0-based) of the script; IndexError past its end."""
        while len(self.changes) <= i:
            self.changes.append(self._next(len(self.changes)))
        return self.changes[i]

    def _next(self, i: int) -> gen.Change:
        kind, size = self._plan[i]
        w = self._w
        if self._jump[i]:
            w.caret = int(self._where[i] * (len(w.elems) + 1))
        if kind == "typing":
            return w.insert(w.caret, [gen.LETTERS[k] for k in self._chars[i, :size]])
        if kind == "backspace":
            w.caret = max(w.caret, size)  # at the text's start: a click right
            return w.delete(w.caret - size, size)
        end = max(1, w.caret)
        start = max(0, end - size)
        mt = self._types[self._marks % len(self._types)]
        self._marks += 1
        attrs = ({"url": gen.URLS[int(self._url[i])]} if mt == "link" else
                 {"id": f"comment-{self._doc}-{self._marks}"} if mt == "comment" else None)
        return w.mark(start, end, mt, attrs)


def changes_within(plan: List[Tuple[str, int]], room: int) -> int:
    """How many of a script's first changes fit ``room`` free element
    slots: deleted chars stay as tombstones, so every typed char takes a
    slot for good."""
    used = 0
    for i, (kind, size) in enumerate(plan):
        used += size if kind == "typing" else 0
        if used > room:
            return i
    return len(plan)


def generate(mix: Dict[str, Any], config: Dict[str, Any], seed: int,
             seconds: float) -> Dict[str, Any]:
    """Per doc a ``doc_len``-char genesis with ``formatted_spans`` spans and
    a script of ``changes_per_writer`` changes holding the mix's exact
    shares of kinds and sizes, so a seed changes order and positions,
    never the amount of work.  The writer starts at a random caret and
    edits there: it types at the caret, deletes before it, or formats the
    chars just before it; before a change it moves its caret to a random
    place with probability ``jump_share``.  ``fits`` holds, per doc, how
    many of its script's first changes fit the doc's ``capacity``."""
    docs, doc_len = config["docs"], config["doc_len"]
    m = int(mix["changes_per_writer"])
    types = mix["mark_types"]
    geneses, scripts, fits = [], [], []
    for d in range(docs):
        r = gen.rng_for(seed, 3, d)
        text = [gen.LETTERS[i] for i in r.integers(len(gen.LETTERS), size=doc_len)]
        g, elems = gen.formatted_genesis(f"w{d:05d}", text, int(mix["formatted_spans"]),
                                         types, r)
        w = gen.Writer(g["actor"], g["ops"][0]["opId"], elems, {g["actor"]: 1},
                       len(g["ops"]))
        w.caret = int(r.integers(doc_len + 1))
        plan = gen.kinds(mix, m, gen.rng_for(seed, 4, d))
        fits.append(changes_within(plan, config["capacity"] - doc_len))
        geneses.append(g)
        scripts.append(Script(w, plan, mix, seed, d))
    return {"geneses": geneses, "scripts": scripts, "fits": fits}
