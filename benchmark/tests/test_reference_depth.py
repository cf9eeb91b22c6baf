"""``benchmark/reference_depth.py`` on hand-made files: racon's admission
rules, the depth cap, and the nodes of an exact graph, each on a case
small enough to count by hand."""

import numpy as np

from benchmark import reference_depth as rd

DRAFT = "ACGTACGTACGTACGTACGT" * 2          # 40 bp: windows of 20


def _files(tmp_path, reads, qual="0"):
    """reads: (name, flag, pos (1-based), cigar, seq on the target's
    strand)."""
    (tmp_path / "draft.fasta").write_text(f">c\n{DRAFT}\n")
    with open(tmp_path / "reads.fastq", "w") as fq, \
            open(tmp_path / "o.sam", "w") as sam:
        sam.write("@HD\tVN:1.6\n@SQ\tSN:c\tLN:40\n")
        for name, flag, pos, cigar, seq in reads:
            q = qual * len(seq)
            fq.write(f"@{name}\n{seq}\n+\n{q}\n")
            sam.write(f"{name}\t{flag}\tc\t{pos}\t60\t{cigar}\t*\t0\t0\t"
                      f"{seq}\t{q}\n")
    return dict(draft_path=str(tmp_path / "draft.fasta"),
                reads_path=str(tmp_path / "reads.fastq"),
                sam_path=str(tmp_path / "o.sam"), window_length=20,
                quality_threshold=10.0, error_threshold=0.3)


def test_a_perfect_read_adds_a_layer_and_no_node(tmp_path):
    d = rd.window_demand(**_files(tmp_path, [("r0", 0, 1, "40M", DRAFT)]))
    assert d["bb_len"].tolist() == [20, 20]
    assert d["layers"].tolist() == [1, 1] and d["capped"].tolist() == [0, 0]
    assert d["layer_bases"].tolist() == [20, 20]
    assert d["nodes"].tolist() == [20, 20]


def test_substitutions_and_insertions_are_counted_once_a_distinct_base(
        tmp_path):
    sub = DRAFT[:5] + "T" + DRAFT[6:]            # C -> T at column 5
    other = DRAFT[:5] + "G" + DRAFT[6:]          # C -> G at column 5
    ins = DRAFT[:10] + "TT" + DRAFT[10:]         # TT after column 9
    ins2 = DRAFT[:10] + "TA" + DRAFT[10:]        # TA after column 9
    d = rd.window_demand(**_files(tmp_path, [
        ("a", 0, 1, "40M", sub), ("b", 0, 1, "40M", sub),
        ("c", 0, 1, "40M", other),
        ("d", 0, 1, "10M2I30M", ins), ("e", 0, 1, "10M2I30M", ins2),
        ("f", 0, 1, "12M3D25M", DRAFT[:12] + DRAFT[15:]),   # no node
    ]))
    # window 0: 20 backbone + T and G at column 5 + (T) at place 1 and
    # (T, A) at place 2 of the gap after column 9
    assert d["nodes"].tolist() == [20 + 2 + 3, 20]
    assert d["layers"].tolist() == [6, 6]
    assert d["layer_bases"][0] == 3 * 20 + 2 * 22 + 17


def test_admission_rules(tmp_path):
    reads = [
        ("whole", 0, 1, "40M", DRAFT),
        # mean quality 5 < -q 10: dropped where it would be a layer
        ("lowq", 0, 1, "40M", DRAFT),
        # a piece of 0 read bases in window 1 (under 2 % of 20 is 0.4:
        # one base is admitted, none is no piece at all)
        ("short", 0, 1, "21M", DRAFT[:21]),
        # error over -e 0.3: 20 read bases against a span of 40
        ("gappy", 0, 1, "10M20D10M", DRAFT[:10] + DRAFT[30:]),
    ]
    files = _files(tmp_path, reads)
    lines = open(files["reads_path"]).read().split("\n")
    lines[7] = "&" * 40                      # lowq's qualities: PHRED 5
    open(files["reads_path"], "w").write("\n".join(lines))
    d = rd.window_demand(**files)
    # window 0: whole + short; window 1: whole + short's single base
    assert d["layers"].tolist() == [2, 2]
    assert d["layer_bases"].tolist() == [40, 21]


def test_the_depth_cap_keeps_the_first_layers_by_begin(tmp_path):
    reads = [(f"r{i}", 0, 1, "40M", DRAFT) for i in range(5)]
    late = DRAFT[:25] + "A" + DRAFT[26:]         # a node in window 1
    reads.append(("late", 0, 24, "17M", late[23:]))
    d = rd.window_demand(**_files(tmp_path, reads), depth_cap=5)
    assert d["layers"].tolist() == [5, 5] and d["capped"].tolist() == [0, 1]
    assert d["nodes"].tolist() == [20, 20]       # the capped layer's too
    d = rd.window_demand(**_files(tmp_path, reads), depth_cap=6)
    assert d["layers"].tolist() == [5, 6] and d["nodes"].tolist() == [20, 21]


def test_a_reverse_strand_read_takes_its_qualities_reversed(tmp_path):
    files = _files(tmp_path, [("rev", 16, 1, "40M", DRAFT)])
    lines = open(files["reads_path"]).read().split("\n")
    # read strand: 20 good bases then 20 bad ones, so on the target's
    # strand window 0 is the bad half
    lines[3] = "5" * 20 + "!" * 20
    open(files["reads_path"], "w").write("\n".join(lines))
    d = rd.window_demand(**files)
    assert d["layers"].tolist() == [0, 1]


def test_every_array_has_a_window_each(tmp_path):
    d = rd.window_demand(**_files(tmp_path, [("r0", 0, 3, "30M",
                                              DRAFT[2:32])]))
    assert all(isinstance(v, np.ndarray) and len(v) == 2
               for v in d.values())
    assert d["layer_bases"].tolist() == [18, 12]
