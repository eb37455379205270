"""Discogs-shaped corpus, generated as flat preorder arrays from a seed.

The benchmark's own copy of the program's synthetic catalog generator
(``repro.data.xmlgen``): the same releases, nodes, labels and text for the
same ``(n_releases, seed)``, node for node (``bench/tests`` checks it), but
built with numpy instead of one Python object per node, so a 100k-release
corpus takes seconds rather than most of a minute.

It reproduces numpy's scalar ``Generator.integers`` stream exactly: PCG64
hands out 32-bit halves of each 64-bit draw (low half first), and a bounded
draw is Lemire's multiply-shift with rejection.  Draws are made in bulk,
decoded for the common case with no rejection, and any release that meets a
rejection is decoded again one draw at a time.

Shape (arXiv:1311.6714, Table III categories): category-1 subtrees
(``images``, ``identifiers``, ``tracklist``) hold text unique to a release,
category-2 leaves (genre, style, country, format name) repeat, and a
release's whole ``formats`` subtree is one of a pool of 20.

Every node carries the keywords of its label and its text, each of which is
a single whitespace-free token here.  A node's keyword ids are its label's
id and, where it has text, the text's id (one id when the two are the same
word).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# the paper's Table I queries, transposed onto the synthetic vocabulary
QUERIES: dict[str, tuple[int, list[str]]] = {
    "Q1": (1, ["image", "uri"]),
    "Q2": (1, ["image", "uri", "release"]),
    "Q3": (1, ["image", "uri", "release", "identifiers"]),
    "Q4": (2, ["vinyl", "electronic"]),
    "Q5": (2, ["vinyl", "electronic", '12"']),
    "Q6": (2, ["vinyl", "electronic", '12"', "uk"]),
    "Q7": (3, ["description", "rpm"]),
    "Q8": (3, ["description", "rpm", "45"]),
    "Q9": (3, ["description", "rpm", "45", '7"']),
}

GENRES = [
    "electronic", "rock", "jazz", "funk", "soul", "pop", "classical",
    "hip-hop", "latin", "reggae", "blues", "folk", "country", "stage", "brass",
]
STYLES = [
    "house", "techno", "ambient", "disco", "punk", "hardcore", "ska", "dub",
    "swing", "bebop", "fusion", "grunge", "synth-pop", "trance", "acid",
    "minimal", "breaks", "garage", "downtempo", "experimental",
]
COUNTRIES = [
    "us", "uk", "germany", "france", "japan", "italy", "netherlands",
    "canada", "spain", "australia", "sweden", "belgium", "brazil", "portugal",
]
FORMAT_POOL: list[tuple[str, list[str]]] = [
    ("vinyl", ['12"', "33", "rpm", "album"]),
    ("vinyl", ['12"', "45", "rpm"]),
    ("vinyl", ['7"', "45", "rpm", "single"]),
    ("vinyl", ['7"', "45", "rpm", "ep"]),
    ("vinyl", ['10"', "78", "rpm"]),
    ("vinyl", ["lp", "album", "reissue"]),
    ("vinyl", ["lp", "album", "repress"]),
    ("cd", ["album"]),
    ("cd", ["album", "reissue"]),
    ("cd", ["single"]),
    ("cd", ["compilation"]),
    ("cassette", ["album"]),
    ("cassette", ["single"]),
    ("file", ["mp3", "320", "kbps"]),
    ("file", ["flac", "album"]),
    ("vinyl", ['12"', "maxi-single", "45", "rpm"]),
    ("vinyl", ['12"', "limited", "edition", "45", "rpm"]),
    ("vinyl", ['7"', "promo", "45", "rpm"]),
    ("cd", ["album", "limited", "edition"]),
    ("dvd", ["pal"]),
]
YEAR_BASE, N_YEARS = 1950, 73
N_ARTISTS, N_LABELS, MAX_TRACKS = 200, 120, 6

# a release's nodes in preorder: (label, parent slot).  Slots 0-23 are
# fixed; the format's descriptions follow (parent slot 23); then the tail
# below, whose parent slots count from the end of the descriptions; then
# four nodes per track.
_HEAD = [
    ("release", -1), ("id", 0), ("status", 0), ("images", 0), ("image", 3),
    ("height", 4), ("width", 4), ("type", 4), ("uri", 4), ("uri150", 4),
    ("artists", 0), ("artist", 10), ("artist-id", 11), ("name", 11),
    ("title", 0), ("labels", 0), ("label", 15), ("catno", 16),
    ("label-name", 16), ("formats", 0), ("format", 19), ("name", 20),
    ("qty", 20), ("descriptions", 20),
]
# tail slot j sits at 24 + d + j; a parent given as ("t", j) is tail slot j
_TAIL = [
    ("genres", 0), ("genre", ("t", 0)), ("styles", 0), ("style", ("t", 2)),
    ("country", 0), ("released", 0), ("identifiers", 0),
    ("identifier", ("t", 6)), ("id-type", ("t", 7)), ("value", ("t", 7)),
    ("tracklist", 0),
]
_TRACK = ["track", "position", "track-title", "duration"]
LABELS = sorted(
    {"releases", "description"} | {l for l, _ in _HEAD}
    | {l for l, _ in _TAIL} | set(_TRACK)
)
_SMALL = sorted(
    set(LABELS) | set(GENRES) | set(STYLES) | set(COUNTRIES)
    | {"accepted", "primary", "barcode"}
    | {n for n, _ in FORMAT_POOL}
    | {d for _, ds in FORMAT_POOL for d in ds if not d.isdigit()}
)

# draws per release before its tracks, with their [low, high) bounds
_BOUNDS = [
    (0, N_ARTISTS), (0, N_LABELS), (0, len(FORMAT_POOL)), (0, MAX_TRACKS),
    (0, len(GENRES)), (0, len(STYLES)), (0, len(COUNTRIES)), (0, N_YEARS),
    (0, 1 << 30),
]
_TRACK_BOUNDS = [(0, 1 << 30), (1, 9), (0, 60)]


@dataclass
class Corpus:
    """A generated catalog in flat preorder arrays.

    ``label_kw[i]`` and ``text_kw[i]`` are node ``i``'s keyword ids (``-1``:
    no text), ``words[id]`` the keyword.  Node 0 is the ``releases`` root.
    """

    n_releases: int
    seed: int
    parent: np.ndarray  # int32
    size: np.ndarray  # int32 subtree sizes
    label_kw: np.ndarray  # int32
    text_kw: np.ndarray  # int32
    words: list[str]

    @property
    def num_nodes(self) -> int:
        return int(self.parent.shape[0])

    def word_ids(self) -> dict[str, int]:
        return {w: i for i, w in enumerate(self.words)}

    def kw_csr(self) -> tuple[np.ndarray, np.ndarray]:
        """(offsets, ids): each node's sorted, distinct keyword ids."""
        lab, txt = self.label_kw, self.text_kw
        two = (txt >= 0) & (txt != lab)
        lens = 1 + two.astype(np.int64)
        offsets = np.zeros(lab.size + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        ids = np.empty(int(offsets[-1]), np.int32)
        lo = np.where(two, np.minimum(lab, txt), lab)
        ids[offsets[:-1]] = lo
        ids[offsets[:-1][two] + 1] = np.maximum(lab, txt)[two]
        return offsets, ids


class _Draws:
    """numpy's scalar bounded-integer stream over a PCG64 generator."""

    def __init__(self, seed: int, n: int):
        self._bitgen = np.random.default_rng(seed).bit_generator
        self.u32 = np.zeros(0, np.uint64)
        self.extend(n)

    def extend(self, n: int) -> None:
        raw = self._bitgen.random_raw((n + 1) // 2).astype(np.uint64)
        halves = np.empty(raw.size * 2, np.uint64)
        halves[0::2] = raw & np.uint64(0xFFFFFFFF)
        halves[1::2] = raw >> np.uint64(32)
        self.u32 = np.concatenate([self.u32, halves])

    def need(self, n: int) -> None:
        while self.u32.size < n:
            self.extend(max(n - self.u32.size, self.u32.size // 2))

    def one(self, pos: int, low: int, high: int) -> tuple[int, int]:
        """The draw at ``pos`` with its rejections: (value, next pos)."""
        n = high - low
        thr = (1 << 32) % n
        while True:
            self.need(pos + 1)
            m = int(self.u32[pos]) * n
            pos += 1
            if (m & 0xFFFFFFFF) >= thr:
                return low + (m >> 32), pos


def _lemire(u32: np.ndarray, low, high) -> tuple[np.ndarray, np.ndarray]:
    """Values and rejection flags of bounded draws, in bulk."""
    n = np.asarray(high, np.uint64) - np.asarray(low, np.uint64)
    m = u32 * n
    thr = (np.uint64(1 << 32) % n).astype(np.uint64)
    rejected = (m & np.uint64(0xFFFFFFFF)) < thr
    return (np.asarray(low, np.int64) + (m >> np.uint64(32)).astype(np.int64),
            rejected)


def _within(counts: np.ndarray) -> np.ndarray:
    """For groups of ``counts`` elements laid end to end, each element's
    index inside its group."""
    return np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)


def _draw_releases(n_releases: int, seed: int) -> dict[str, np.ndarray]:
    """Every release's random fields, as numpy's scalar calls give them."""
    per = len(_BOUNDS)
    d = _Draws(seed, n_releases * (per + 3 * (MAX_TRACKS + 1) // 2 + 2))
    head = np.zeros((n_releases, per), np.int64)
    tracks: list[np.ndarray] = []  # per release: [n_tracks, 3]
    lows = np.array([lo for lo, _ in _BOUNDS])
    highs = np.array([hi for _, hi in _BOUNDS])
    tlows = np.array([lo for lo, _ in _TRACK_BOUNDS])
    thighs = np.array([hi for _, hi in _TRACK_BOUNDS])
    r, pos = 0, 0
    while r < n_releases:
        # optimistic pass: no rejection from release r on; positions follow
        # from each release's track count (its fourth draw)
        starts = np.zeros(n_releases - r, np.int64)
        p = pos
        ntr_hi = MAX_TRACKS
        for i in range(n_releases - r):
            starts[i] = p
            d.need(p + per)
            nt = 1 + ((int(d.u32[p + 3]) * ntr_hi) >> 32)
            p += per + 3 * nt
        d.need(p)
        idx = starts[:, None] + np.arange(per)
        vals, rej = _lemire(d.u32[idx], lows, highs)
        ntr = 1 + vals[:, 3]
        tpos = np.repeat(starts + per, ntr) + 3 * _within(ntr)
        tidx = tpos[:, None] + np.arange(3)
        tvals, trej = _lemire(d.u32[tidx], tlows, thighs)
        bad = rej.any(axis=1)
        tbad = np.zeros(n_releases - r, bool)
        owner = np.repeat(np.arange(n_releases - r), ntr)
        np.logical_or.at(tbad, owner, trej.any(axis=1))
        first = np.flatnonzero(bad | tbad)
        stop = int(first[0]) if first.size else n_releases - r
        head[r: r + stop] = vals[:stop]
        splits = np.cumsum(ntr[:stop])[:-1]
        tracks.extend(np.split(tvals[: int(ntr[:stop].sum())], splits)
                      if stop else [])
        r += stop
        if r == n_releases:
            break
        # release r meets a rejection: decode it one draw at a time
        pos = int(starts[stop])
        row = []
        for lo, hi in _BOUNDS:
            v, pos = d.one(pos, lo, hi)
            row.append(v)
        head[r] = row
        trows = []
        for _ in range(1 + row[3]):
            trow = []
            for lo, hi in _TRACK_BOUNDS:
                v, pos = d.one(pos, lo, hi)
                trow.append(v)
            trows.append(trow)
        tracks.append(np.asarray(trows, np.int64))
        r += 1
    names = ["artist", "label", "fmt", "ntr", "genre", "style", "country",
             "year", "title_r"]
    out = {k: head[:, j] for j, k in enumerate(names)}
    out["ntr"] = out["ntr"] + 1
    allt = np.concatenate(tracks) if tracks else np.zeros((0, 3), np.int64)
    out["trk_r"], out["dur_m"], out["dur_s"] = allt[:, 0], allt[:, 1], allt[:, 2]
    return out


def generate(n_releases: int, seed: int) -> Corpus:
    """The catalog ``repro.data.generate_discogs_tree`` builds, as arrays."""
    f = _draw_releases(n_releases, seed)
    rid = np.arange(n_releases, dtype=np.int64)
    ndesc = np.array([len(ds) for _, ds in FORMAT_POOL])[f["fmt"]]
    ntr = f["ntr"]
    rsize = len(_HEAD) + ndesc + len(_TAIL) + 4 * ntr
    start = 1 + np.concatenate([[0], np.cumsum(rsize)[:-1]])  # release node
    n = 1 + int(rsize.sum())

    small = {w: i for i, w in enumerate(_SMALL)}
    parent = np.full(n, -1, np.int32)
    label = np.full(n, small["releases"], np.int32)
    text = np.full(n, -1, np.int64)  # word ids assigned below, by category
    cat = np.zeros(n, np.int8)  # 0 small word, 1 int, 2 unique string
    val = np.zeros(n, np.int64)  # category payload (int value / string row)

    # -- fixed head slots
    for slot, (lab, par) in enumerate(_HEAD):
        at = start + slot
        label[at] = small[lab]
        parent[at] = start + par if par >= 0 else 0
    # -- descriptions
    dj = _within(ndesc)
    dst = np.repeat(start + len(_HEAD), ndesc) + dj
    label[dst] = small["description"]
    parent[dst] = np.repeat(start + len(_HEAD) - 1, ndesc)
    # -- tail slots
    tail0 = start + len(_HEAD) + ndesc
    for j, (lab, par) in enumerate(_TAIL):
        at = tail0 + j
        label[at] = small[lab]
        parent[at] = tail0 + par[1] if isinstance(par, tuple) else start
    # -- tracks
    tr_release = np.repeat(np.arange(n_releases), ntr)
    tnum = _within(ntr)
    tstart = tail0[tr_release] + len(_TAIL) + 4 * tnum
    for c, lab in enumerate(_TRACK):
        label[tstart + c] = small[lab]
        parent[tstart + c] = tstart if c else tail0[tr_release] + len(_TAIL) - 1

    # -- text: small words
    text[start + 2] = small["accepted"]
    text[start + 7] = small["primary"]
    fmt_names = np.array([small[nm] for nm, _ in FORMAT_POOL])
    text[start + 21] = fmt_names[f["fmt"]]
    text[tail0 + 1] = np.array([small[g] for g in GENRES])[f["genre"]]
    text[tail0 + 3] = np.array([small[s] for s in STYLES])[f["style"]]
    text[tail0 + 4] = np.array([small[c] for c in COUNTRIES])[f["country"]]
    text[tail0 + 8] = small["barcode"]
    # descriptions: small words or integers
    desc_flat = [dd for _, ds in FORMAT_POOL for dd in ds]
    desc_off = np.concatenate([[0], np.cumsum([len(ds) for _, ds in FORMAT_POOL])])
    dword = np.repeat(desc_off[f["fmt"]], ndesc) + dj
    desc_is_int = np.array([w.isdigit() for w in desc_flat])
    desc_small = np.array([small.get(w, -1) for w in desc_flat])
    desc_int = np.array([int(w) if w.isdigit() else 0 for w in desc_flat])
    di = desc_is_int[dword]
    text[dst[~di]] = desc_small[dword[~di]]

    # -- text: integers (one keyword per distinct decimal string)
    def put_int(at, values):
        cat[at] = 1
        val[at] = values

    put_int(start + 1, rid)
    put_int(start + 5, 400 + rid % 1213)
    put_int(start + 6, 400 + (rid * 7) % 1217)
    put_int(start + 12, f["artist"])
    put_int(start + 22, np.ones(n_releases, np.int64))
    put_int(dst[di], desc_int[dword[di]])
    put_int(tail0 + 5, YEAR_BASE + f["year"])
    put_int(tstart + 1, tnum + 1)

    # -- text: strings of other shapes, each family kept apart by its form
    fam_words: list[list[str]] = []

    def put_family(at, keys, render):
        """Nodes ``at`` carry the word ``render(key)``: one id per key."""
        uniq, inv = np.unique(keys, return_inverse=True)
        cat[at] = 2
        val[at] = sum(len(w) for w in fam_words) + inv
        fam_words.append([render(k) for k in uniq.tolist()])

    put_family(start + 8, rid, lambda r: f"img-{r}.jpg")
    put_family(start + 9, rid, lambda r: f"img-{r}-150.jpg")
    put_family(start + 13, f["artist"], lambda a: f"artist-{a}")
    put_family(start + 14, rid * (1 << 30) + f["title_r"],
               lambda k: f"title-{k >> 30}-{k & ((1 << 30) - 1)}")
    put_family(start + 17, f["label"] * 97 + rid % 97,
               lambda k: f"cat-{k // 97}-{k % 97}")
    put_family(start + 18, f["label"], lambda lb: f"label-{lb}")
    put_family(tail0 + 9, rid, lambda r: f"{r:012d}")
    tkey = (tr_release * 8 + tnum) * (1 << 30) + f["trk_r"]
    put_family(tstart + 2, tkey,
               lambda k: f"trk-{(k >> 30) // 8}-{(k >> 30) % 8}-"
                         f"{k & ((1 << 30) - 1)}")
    put_family(tstart + 3, f["dur_m"] * 60 + f["dur_s"],
               lambda k: f"{k // 60}:{k % 60:02d}")

    # -- number the words: small words, then integers, then the families
    ints = np.unique(val[cat == 1])
    int_base = len(_SMALL)
    fam_base = int_base + ints.size
    is_int = cat == 1
    text[is_int] = int_base + np.searchsorted(ints, val[is_int])
    text[cat == 2] = fam_base + val[cat == 2]
    words = list(_SMALL) + [str(v) for v in ints.tolist()]
    for fw in fam_words:
        words.extend(fw)

    # subtree sizes of the inner nodes; every other node is a leaf
    size = np.ones(n, np.int32)
    size[0] = n
    size[start] = rsize
    for slot, sz in ((3, 7), (4, 6), (10, 4), (11, 3), (15, 4), (16, 3)):
        size[start + slot] = sz
    size[start + 19] = 5 + ndesc  # formats
    size[start + 20] = 4 + ndesc  # format
    size[start + 23] = 1 + ndesc  # descriptions
    for j, sz in ((0, 2), (2, 2), (6, 4), (7, 3)):
        size[tail0 + j] = sz
    size[tail0 + 10] = 1 + 4 * ntr  # tracklist
    size[tstart] = 4
    return Corpus(n_releases, seed, parent, size, label, text.astype(np.int32),
                  words)
