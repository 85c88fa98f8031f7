"""Record readers beyond CSV + the parallel transform executor.

Reference parity (datavec-api records/reader/impl/** and datavec-spark):
  * LineRecordReader.java — one record per line.
  * regex/RegexLineRecordReader.java — regex with capture groups → columns.
  * jackson/JacksonLineRecordReader.java — one JSON document per line,
    field-selected into columns.
  * misc/SVMLightRecordReader.java — sparse `label idx:val ...` rows.
  * csv/CSVSequenceRecordReader.java — one sequence (list of timesteps) per
    file / blank-line-separated block.
  * SparkTransformExecutor.java — cluster-parallel TransformProcess
    execution; here a fork-based multiprocess executor (the single-host
    analog — the reference's Spark local[N] mode).
"""

from __future__ import annotations

import csv
import io
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np


def _read_text(source: Union[str, io.TextIOBase]) -> str:
    if isinstance(source, str) and "\n" not in source and os.path.exists(source):
        with open(source) as f:
            return f.read()
    return source if isinstance(source, str) else source.read()


class LineRecordReader:
    """records/reader/impl/LineRecordReader.java: each line is a
    single-column record."""

    def __init__(self, skip_lines: int = 0):
        self.skip_lines = skip_lines

    def read(self, source) -> List[List[str]]:
        lines = _read_text(source).splitlines()
        return [[ln] for ln in lines[self.skip_lines:]]


class RegexLineRecordReader:
    """records/reader/impl/regex/RegexLineRecordReader.java: each line must
    match ``pattern``; capture groups become the record's columns."""

    def __init__(self, pattern: str, skip_lines: int = 0):
        self.pattern = re.compile(pattern)
        self.skip_lines = skip_lines

    def read(self, source) -> List[List[str]]:
        out = []
        for i, ln in enumerate(_read_text(source).splitlines()):
            if i < self.skip_lines or not ln:
                continue
            m = self.pattern.match(ln)
            if m is None:
                raise ValueError(
                    f"line {i} does not match pattern "
                    f"{self.pattern.pattern!r}: {ln!r}")
            out.append(list(m.groups()))
        return out


class JacksonLineRecordReader:
    """records/reader/impl/jackson/JacksonLineRecordReader.java: one JSON
    object per line; ``field_selection`` lists the keys (in order) to pull
    into columns — missing keys take the per-field default (None)."""

    def __init__(self, field_selection: Sequence[str],
                 defaults: Optional[Dict[str, Any]] = None):
        self.fields = list(field_selection)
        self.defaults = defaults or {}

    def read(self, source) -> List[List[Any]]:
        out = []
        for ln in _read_text(source).splitlines():
            if not ln.strip():
                continue
            doc = json.loads(ln)
            out.append([doc.get(f, self.defaults.get(f)) for f in self.fields])
        return out


class SVMLightRecordReader:
    """records/reader/impl/misc/SVMLightRecordReader.java: sparse
    ``label idx:val idx:val ...`` rows → dense feature vector + label.
    ``num_features`` fixes the dense width; ``zero_based`` controls whether
    indices start at 0 (default: 1-based, the SVMLight convention)."""

    def __init__(self, num_features: int, zero_based: bool = False):
        self.num_features = num_features
        self.zero_based = zero_based

    def read(self, source) -> List[List[float]]:
        out = []
        for ln in _read_text(source).splitlines():
            ln = ln.split("#")[0].strip()
            if not ln:
                continue
            parts = ln.split()
            label = float(parts[0])
            feats = np.zeros(self.num_features, np.float32)
            for tok in parts[1:]:
                idx, val = tok.split(":")
                j = int(idx) - (0 if self.zero_based else 1)
                if not 0 <= j < self.num_features:
                    raise ValueError(f"feature index {idx} out of range "
                                     f"for num_features={self.num_features}")
                feats[j] = float(val)
            out.append(list(feats) + [label])
        return out

    def read_dataset(self, source):
        """Dense (features, labels) arrays (the RecordReaderDataSetIterator
        shortcut for SVMLight sources)."""
        rows = self.read(source)
        arr = np.asarray(rows, np.float32)
        return arr[:, :-1], arr[:, -1]


class CSVSequenceRecordReader:
    """records/reader/impl/csv/CSVSequenceRecordReader.java: sequences of
    CSV timesteps — one sequence per file, or blank-line-separated blocks
    when reading a single source."""

    def __init__(self, skip_lines: int = 0, delimiter: str = ","):
        self.skip_lines = skip_lines
        self.delimiter = delimiter

    def read_sequence(self, source) -> List[List[str]]:
        rows = list(csv.reader(io.StringIO(_read_text(source)),
                               delimiter=self.delimiter))
        return [r for r in rows[self.skip_lines:] if r]

    def read(self, sources: Union[str, Iterable[Any]]) -> List[List[List[str]]]:
        if isinstance(sources, (list, tuple)):
            return [self.read_sequence(s) for s in sources]
        text = _read_text(sources)
        blocks = re.split(r"\n\s*\n", text.strip())
        return [self.read_sequence(b) for b in blocks if b.strip()]


# ---------------------------------------------------------------------------
# Parallel transform execution (datavec-spark SparkTransformExecutor role)
# ---------------------------------------------------------------------------

_FORK_TP = None  # set in the child via fork inheritance


def _run_chunk(chunk):
    return _FORK_TP.execute(chunk)


def _keep_off_device():
    # pool initializer, forked or spawned: workers only run host-side
    # record transforms, and a chip has one owner — the parent may hold it
    # (or want it later), so a child that imports jax must get the CPU
    os.environ["JAX_PLATFORMS"] = "cpu"


def _run_chunk_spawn(args):
    tp, chunk = args
    return tp.execute(chunk)


class ParallelTransformExecutor:
    """SparkTransformExecutor.execute analog on one host: multiprocess map
    over contiguous record chunks (the reference's Spark local[N] mode).

    Start-method choice is a correctness matter, not a tuning knob:
      * fork is used only while the process is still single-threaded
        (before jax import) — forking a multi-threaded process can deadlock
        on locks held by jax/XLA background threads. Fork inheritance
        carries closure-based conditions/filters unchanged.
      * once jax is loaded, workers are spawned fresh; the
        TransformProcess must then be picklable — every
        step/condition in the built-in DSL is. An unpicklable process
        (user lambdas) falls back to in-process execution.
    Either way the pool's initializer pins the workers to the CPU backend.
    Small inputs always run inline — process spin-up dominates them."""

    def __init__(self, workers: int = 0, min_parallel: int = 512):
        self.workers = workers or (os.cpu_count() or 2)
        self.min_parallel = min_parallel

    def execute(self, records: List[List[Any]], tp) -> List[List[Any]]:
        import multiprocessing as mp
        import pickle
        import sys

        if (len(records) < self.min_parallel
                or not hasattr(os, "fork")):
            return tp.execute(records)
        n = min(self.workers, max(1, len(records) // 64))
        size = -(-len(records) // n)
        # CONTIGUOUS chunks: filters may drop records, so per-chunk result
        # lengths vary — concatenation in chunk order preserves the
        # reference's record order regardless
        chunks = [records[i * size:(i + 1) * size] for i in range(n)]
        if "jax" not in sys.modules:
            global _FORK_TP
            _FORK_TP = tp
            try:
                ctx = mp.get_context("fork")
                with ctx.Pool(n, initializer=_keep_off_device) as pool:
                    results = pool.map(_run_chunk, chunks)
            finally:
                _FORK_TP = None
        else:
            try:
                pickle.dumps(tp)
            except Exception:
                return tp.execute(records)  # closures: stay in-process
            ctx = mp.get_context("spawn")
            with ctx.Pool(n, initializer=_keep_off_device) as pool:
                results = pool.map(_run_chunk_spawn,
                                   [(tp, c) for c in chunks])
        return [r for res in results for r in res]


class ExcelRecordReader:
    """datavec-excel ExcelRecordReader analog: .xlsx parsing with the
    stdlib only (an xlsx IS a zip of XML — no poi/openpyxl dependency).
    Reads the first worksheet (or ``sheet_index``) into rows of typed cells
    (numbers become float, shared/inline strings str, booleans bool)."""

    _NS = "{http://schemas.openxmlformats.org/spreadsheetml/2006/main}"

    def __init__(self, sheet_index: int = 0, skip_rows: int = 0):
        self.sheet_index = sheet_index
        self.skip_rows = skip_rows

    def read(self, path: str) -> List[List[Any]]:
        import xml.etree.ElementTree as ET
        import zipfile

        ns = self._NS
        with zipfile.ZipFile(path) as z:
            shared: List[str] = []
            if "xl/sharedStrings.xml" in z.namelist():
                root = ET.fromstring(z.read("xl/sharedStrings.xml"))
                for si in root.findall(f"{ns}si"):
                    shared.append("".join(t.text or ""
                                          for t in si.iter(f"{ns}t")))
            import re as _re

            def _sheet_no(nm):
                m = _re.search(r"sheet(\d+)\.xml$", nm)
                return int(m.group(1)) if m else 0

            # numeric sort: lexicographic puts sheet10 before sheet2
            sheets = sorted((n for n in z.namelist()
                             if n.startswith("xl/worksheets/sheet")
                             and n.endswith(".xml")), key=_sheet_no)
            if self.sheet_index >= len(sheets):
                raise ValueError(
                    f"xlsx has {len(sheets)} sheets; index "
                    f"{self.sheet_index} out of range")
            root = ET.fromstring(z.read(sheets[self.sheet_index]))
        def _col_index(ref) -> Optional[int]:
            # "BC12" -> column 54 (0-based); writers omit EMPTY cells, so
            # alignment must come from the cell reference, not cell order
            if not ref:
                return None
            col = 0
            for ch in ref:
                if ch.isalpha():
                    col = col * 26 + (ord(ch.upper()) - ord("A") + 1)
                else:
                    break
            return col - 1 if col else None

        rows: List[List[Any]] = []
        for row in root.iter(f"{ns}row"):
            out: List[Any] = []
            for c in row.findall(f"{ns}c"):
                t = c.get("t", "n")
                v = c.find(f"{ns}v")
                if t == "inlineStr":
                    is_el = c.find(f"{ns}is")
                    val = ("".join(tt.text or ""
                                   for tt in is_el.iter(f"{ns}t"))
                           if is_el is not None else "")
                elif v is None:
                    val = None
                elif t == "s":
                    val = shared[int(v.text)]
                elif t == "b":
                    val = v.text == "1"
                else:
                    val = float(v.text)
                idx = _col_index(c.get("r"))
                if idx is None:
                    out.append(val)
                else:
                    while len(out) < idx:
                        out.append(None)  # omitted empty cells
                    if len(out) == idx:
                        out.append(val)
                    else:
                        out[idx] = val
            rows.append(out)
        return rows[self.skip_rows:]


class SQLRecordReader:
    """datavec-jdbc JDBCRecordReader analog over any DB-API 2.0 connection
    (sqlite3 in the stdlib plays the role of the JDBC driver): run a query,
    stream rows as records; ``schema()`` derives a datavec Schema from the
    cursor description + first row's types."""

    def __init__(self, connection, query: str):
        self.conn = connection
        self.query = query
        self._cache: Optional[List[List[Any]]] = None

    def read(self) -> List[List[Any]]:
        if self._cache is not None:
            return self._cache
        cur = self.conn.cursor()
        try:
            cur.execute(self.query)
            self._description = cur.description
            self._cache = [list(r) for r in cur.fetchall()]
            return self._cache
        finally:
            cur.close()

    def schema(self):
        from deeplearning4j_tpu.datavec.transform import Schema

        rows = self.read()
        b = Schema.Builder()
        names = [d[0] for d in (self._description or [])]
        first = rows[0] if rows else []
        for i, name in enumerate(names):
            v = first[i] if i < len(first) else None
            if isinstance(v, bool):
                b.add_column_categorical(name, "false", "true")
            elif isinstance(v, int):
                b.add_column_long(name)
            elif isinstance(v, float):
                b.add_column_double(name)
            else:
                b.add_column_string(name)
        return b.build()


def haversine_km(lat1, lon1, lat2, lon2) -> float:
    """Great-circle distance (datavec-geo CoordinatesDistanceTransform
    math)."""
    import math

    r = 6371.0088
    p1, p2 = math.radians(lat1), math.radians(lat2)
    dp = math.radians(lat2 - lat1)
    dl = math.radians(lon2 - lon1)
    a = (math.sin(dp / 2) ** 2
         + math.cos(p1) * math.cos(p2) * math.sin(dl / 2) ** 2)
    return 2 * r * math.asin(math.sqrt(a))
