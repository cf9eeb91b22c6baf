"""What fragment correction (``racon -f``, ``PolisherType::kF``) must
select, recomputed from the input files alone.

Independent of both polishing paths: plain Python over the PAF and the
sequence files, no ``racon_tpu`` import but ``native.edit_distance`` (the
yardstick every accuracy number already uses).  The tests hold the host
path and the device path to it, not only to each other.  The rules, from
upstream's ``src/polisher.cpp`` (``initialize``, ``polish``):

* an overlap survives when both names are known, its error
  ``1 - min(q span, t span) / max(q span, t span)`` is at most ``-e``, and
  query and target are not the same sequence.  In contig polishing (kC)
  only the longest overlap per query survives; in kF every one does;
* every target is cut into windows of ``-w`` bases, the last one shorter;
* a target's ``RC`` tag counts the surviving overlaps that name it as
  target, ``LN`` is the corrected length, and the name gains an ``r``;
* a target none of whose windows was polished is dropped from the output
  (``drop_unpolished``), so one without a surviving overlap always is.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_TAGS = re.compile(r"^(?P<name>\S+?)(?P<r>r?) LN:i:(?P<ln>\d+) "
                   r"RC:i:(?P<rc>\d+) XC:f:(?P<xc>[0-9.]+)$")


def read_sequences(path: str) -> list:
    """[(name, bases)] of a FASTA or FASTQ file (one line per record
    part, as the benchmark's generators write them), in file order."""
    out = []
    with open(path) as f:
        lines = [line.rstrip("\n") for line in f]
    step = 4 if lines and lines[0].startswith("@") else 2
    for i in range(0, len(lines) - 1, step):
        out.append((lines[i][1:].split()[0], lines[i + 1].upper()))
    return out


@dataclass(frozen=True)
class Overlap:
    q: str
    q_len: int
    q_begin: int
    q_end: int
    strand: bool
    t: str
    t_len: int
    t_begin: int
    t_end: int

    @property
    def error(self) -> float:
        spans = (self.q_end - self.q_begin, self.t_end - self.t_begin)
        return 1.0 - min(spans) / max(spans)

    def dual(self) -> tuple:
        """What the same meeting reads like from the other side."""
        return (self.t, self.t_begin, self.t_end, self.strand, self.q,
                self.q_begin, self.q_end)

    def key(self) -> tuple:
        return (self.q, self.q_begin, self.q_end, self.strand, self.t,
                self.t_begin, self.t_end)


def read_paf(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            c = line.rstrip("\n").split("\t")
            out.append(Overlap(c[0], int(c[1]), int(c[2]), int(c[3]),
                               c[4] == "-", c[5], int(c[6]), int(c[7]),
                               int(c[8])))
    return out


@dataclass
class Expected:
    """What kF must select for one job."""

    parsed: int                                   # PAF lines
    kept: list                                    # surviving Overlaps
    targets: list                                 # names, output order
    coverage: dict = field(default_factory=dict)  # target -> RC
    windows: dict = field(default_factory=dict)   # target -> [lengths]

    @property
    def window_lengths(self) -> list:
        """Every window's backbone length, in the pipeline's order."""
        return [n for t in self.targets for n in self.windows[t]]

    @property
    def output_targets(self) -> list:
        """Targets that can appear in the output (``drop_unpolished``)."""
        return [t for t in self.targets if self.coverage[t]]


def expect(reads_path: str, paf_path: str, targets_path: str, *,
           window_length: int, error_threshold: float) -> Expected:
    reads = dict(read_sequences(reads_path))
    targets = read_sequences(targets_path)
    names = {name for name, _ in targets}
    overlaps = read_paf(paf_path)
    kept = [o for o in overlaps
            if o.q in reads and o.t in names and o.q != o.t
            and o.error <= error_threshold]
    out = Expected(parsed=len(overlaps), kept=kept,
                   targets=[name for name, _ in targets])
    for name, bases in targets:
        out.coverage[name] = 0
        out.windows[name] = [min(window_length, len(bases) - j)
                             for j in range(0, len(bases), window_length)]
    for o in kept:
        out.coverage[o.t] += 1
    return out


def undualled(overlaps: list) -> list:
    """Overlaps whose dual (the same meeting with query and target
    exchanged) is not in the list: empty for a dual all-vs-all PAF."""
    have = {o.key() for o in overlaps}
    return [o for o in overlaps if o.dual() not in have]


def parse_record_name(name: str) -> dict:
    """A polished record's name: the input name, whether it carries kF's
    ``r``, and its LN / RC / XC tags."""
    m = _TAGS.match(name)
    if m is None:
        raise ValueError(f"not a polished record's name: {name!r}")
    return {"name": m["name"], "r": m["r"] == "r", "LN": int(m["ln"]),
            "RC": int(m["rc"]), "XC": float(m["xc"])}


def edits_to_truth(records: list, truth: list) -> dict:
    """{input name: edit distance of its record to its true bases};
    ``records`` are (name with or without tags, bases), ``truth`` is
    [(name, bases)] as ``genome.fasta`` holds them."""
    from racon_tpu import native

    true = dict(truth)
    out = {}
    for name, bases in records:
        m = _TAGS.match(name)
        key = m["name"] if m else name
        out[key] = native.edit_distance(bases.encode(), true[key].encode())
    return out
