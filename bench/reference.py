"""The plain reference: what a coherent key-value store must answer.

It imports nothing of the program.  It keeps, per record, the latest
version and the write tag whose bytes that version holds, and checks
each batch the plane served against these rules, the guarantees the
configurations state (SELCC: sequential consistency over coherent
write-back caches, every acknowledged write read back):

* the updates of a record in one batch take the versions ``v0 + 1 ..
  v0 + m``, each once, where ``v0`` is the record's version before the
  batch and ``m`` its number of updates in the batch;
* a read returns a version between ``v0`` and ``v0 + m`` (it sees every
  write acknowledged before its batch was submitted, and at most the
  writes of its own batch), with exactly the bytes that version holds:
  the seed's version-0 bytes, or the bytes of the update that made it;
* an update's reply carries the bytes of a version of that record no
  older than its own and no newer than the batch's last;
* after the window, a record read back from a node other than its last
  writer returns its last version and bytes (the write-back flush).

A batch the plane refused (``failed``) changes nothing.  Every check
that does not hold counts one mismatch; a correct plane has none.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .ycsb import init_bytes, write_bytes


@dataclass
class Served:
    """One batch as it was sent and answered."""

    node: np.ndarray
    line: np.ndarray
    is_write: np.ndarray
    tags: np.ndarray
    version: np.ndarray | None = None
    data: np.ndarray | None = None
    rounds: int = 0
    failed: bool = False

    @property
    def ops(self) -> int:
        """Ops the batch completed: its slots with a line, unless it
        was refused."""
        return 0 if self.failed else int((self.line >= 0).sum())


@dataclass
class Reference:
    n_records: int
    width: int
    seed: int
    version: np.ndarray = field(init=False)
    last_tag: np.ndarray = field(init=False)
    last_node: np.ndarray = field(init=False)

    def __post_init__(self):
        self.version = np.zeros(self.n_records, np.int64)
        self.last_tag = np.zeros(self.n_records, np.int64)
        self.last_node = np.full(self.n_records, -1, np.int64)

    def bytes_of_tags(self, lines: np.ndarray, tags: np.ndarray):
        """Lanes of each (line, tag): the version-0 bytes for tag 0."""
        out = np.empty((lines.size, self.width), np.int32)
        init = tags == 0
        if init.any():
            out[init] = init_bytes(self.seed, lines[init], self.width)
        if (~init).any():
            out[~init] = write_bytes(self.seed, tags[~init], self.width)
        return out

    def check_batch(self, b: Served) -> int:
        """Mismatches in one served batch; then apply its writes."""
        if b.failed:
            return 0
        line = b.line.astype(np.int64)
        isw = b.is_write.astype(bool)
        ver = b.version.astype(np.int64)
        v0 = self.version[line]
        bad = np.zeros(line.size, bool)

        # updates: per record the versions v0+1 .. v0+m, each once
        w = np.flatnonzero(isw)
        order = w[np.lexsort((ver[w], line[w]))]
        lw = line[order]
        start = np.r_[True, lw[1:] != lw[:-1]] if lw.size else lw
        first = np.maximum.accumulate(
            np.where(start, np.arange(lw.size), 0)) if lw.size else lw
        rank = np.arange(lw.size) - first
        bad[order] |= ver[order] != v0[order] + rank + 1
        m = np.zeros(line.size, np.int64)
        if lw.size:
            uniq, counts = np.unique(lw, return_counts=True)
            m_of = dict(zip(uniq.tolist(), counts.tolist()))
            m = np.array([m_of.get(int(x), 0) for x in line], np.int64)
        v1 = v0 + m

        # the tag behind every version a reply may name
        tag_of = {(int(line[i]), int(ver[i])): int(b.tags[i]) for i in w}

        def tag(ln: int, v: int) -> int | None:
            if v == int(self.version[ln]):
                return int(self.last_tag[ln])
            return tag_of.get((ln, v))

        # reads: v0 <= v <= v1, with that version's bytes
        r = np.flatnonzero(~isw)
        in_range = (ver[r] >= v0[r]) & (ver[r] <= v1[r])
        bad[r[~in_range]] = True
        rr = r[in_range]
        tags = [tag(int(line[i]), int(ver[i])) for i in rr]
        known = np.array([t is not None for t in tags], bool)
        bad[rr[~known]] = True
        rr = rr[known]
        if rr.size:
            want = self.bytes_of_tags(
                line[rr], np.array([t for t in tags if t is not None],
                                   np.int64))
            bad[rr] |= (b.data[rr] != want).any(axis=1)

        # update replies: the bytes of a version in [own, v1]
        for i in w:
            if bad[i]:
                continue
            ln = int(line[i])
            cands = [t for v in range(int(ver[i]), int(v1[i]) + 1)
                     if (t := tag(ln, v)) is not None]
            rows = self.bytes_of_tags(np.full(len(cands), ln),
                                      np.array(cands, np.int64))
            if not (rows == b.data[i][None, :]).all(axis=1).any():
                bad[i] = True

        # apply: each written record now holds its batch's last version
        for i in w:
            ln = int(line[i])
            if int(ver[i]) == int(v1[i]) and not bad[i]:
                self.last_tag[ln] = int(b.tags[i])
                self.last_node[ln] = int(b.node[i])
        touched = line[w]
        self.version[touched] = v1[w]
        return int(bad.sum())

    def readback_plan(self, rng: np.random.Generator, n_max: int,
                      n_nodes: int):
        """Records to read back after the window: up to ``n_max`` of
        those written, the most written first, then a seeded sample of
        the rest.  Returns (lines, reader nodes): each record is read
        from the node after its last writer, so a dirty copy elsewhere
        has to be flushed to memory first."""
        written = np.flatnonzero(self.version > 0)
        if written.size > n_max:
            hot = written[np.argsort(-self.version[written],
                                     kind="stable")[:n_max // 2]]
            rest = np.setdiff1d(written, hot)
            pick = rng.choice(rest, n_max - hot.size, replace=False)
            written = np.sort(np.concatenate([hot, pick]))
        nodes = (self.last_node[written] + 1) % n_nodes
        return written.astype(np.int32), nodes.astype(np.int32)

    def check_readback(self, lines: np.ndarray, version: np.ndarray,
                       data: np.ndarray) -> int:
        """Mismatches among records read back after the window."""
        ln = lines.astype(np.int64)
        want = self.bytes_of_tags(ln, self.last_tag[ln])
        bad = (version.astype(np.int64) != self.version[ln]) \
            | (data != want).any(axis=1)
        return int(bad.sum())
