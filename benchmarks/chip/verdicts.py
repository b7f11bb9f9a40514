"""Plain reference of the straggler verdict, read from the window journal.

The driver's ``--journal`` file holds every window it submitted for
analysis.  This module decodes it by the documented formats alone (journal
records ``PDWJ``, snapshot blobs ``PDWS``, docs/wire-format.md), and gives
each window the paper's external-bottleneck verdict computed plainly:

  * each rank's vector is its inclusive CPU time per region;
  * two ranks are neighbours when their distance is under 10% of the
    first one's vector length; a rank with at least 2 neighbours
    (itself included) is a core point, and clusters are the sets that
    core points reach (the paper's density clustering);
  * the largest cluster is the healthy majority, every other rank a
    straggler;
  * severity S = the largest distance between two ranks over the
    shortest vector length (the paper's Eq. 2).

It imports nothing of the system under test.
"""
from __future__ import annotations

import json
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

_REC = struct.Struct("<4sQIII")          # magic, seq, label len, blob len, crc
_WIRE = struct.Struct("<4sHI")           # magic, version, header len
LOCATE = ("cpu_time", "wall_time", "cycles", "instructions")
EPS_FRACTION = 0.10
COUNT_THRESHOLD = 2


def read_journal(path: str) -> List[Tuple[Optional[str], bytes]]:
    """(label, blob) of every whole record, in file order."""
    with open(path, "rb") as f:
        data = f.read()
    out, pos = [], 0
    while pos + _REC.size <= len(data):
        magic, _, lab_len, blob_len, _ = _REC.unpack_from(data, pos)
        if magic != b"PDWJ":
            break
        body = pos + _REC.size
        end = body + lab_len + blob_len
        if end > len(data):
            break
        lab = data[body:body + lab_len].decode() if lab_len else None
        out.append((lab, data[body + lab_len:end]))
        pos = end
    return out


def cpu_times(blob: bytes) -> np.ndarray:
    """The (ranks, regions) CPU-time matrix of one PDWS snapshot blob."""
    magic, _, hlen = _WIRE.unpack_from(blob)
    if magic != b"PDWS":
        raise ValueError(f"not a snapshot blob: {magic!r}")
    if blob[-8:-4] == b"PDWC":
        blob = blob[:-8]
    head = json.loads(blob[_WIRE.size:_WIRE.size + hlen])
    m, n = head["n_ranks"], head["n_regions"]
    fields = [(f, "<f8") for f in LOCATE]
    fields += [(spec[0], "<f8") for spec in head["schema_spec"]]
    fields += [("region_id", "<u2"), ("rank", "<u4"), ("flags", "<u2")]
    pad = max(0, 3 * 8 * len(LOCATE) - np.dtype(fields).itemsize)
    if pad:
        fields.append(("_pad", f"V{pad}"))
    dt = np.dtype(fields)
    payload = blob[_WIRE.size + hlen:]
    if len(payload) != 8 * m + dt.itemsize * m * n:
        raise ValueError("snapshot payload length does not match its header")
    cells = np.frombuffer(payload[8 * m:], dtype=dt).reshape(m, n)
    return np.asarray(cells["cpu_time"], dtype=np.float64)


def verdict(perf: np.ndarray) -> Dict:
    """Stragglers and severity of one window's (ranks, regions) matrix."""
    m = perf.shape[0]
    diff = perf[:, None, :] - perf[None, :, :]
    dist = np.sqrt(np.sum(diff * diff, axis=-1))
    length = np.sqrt(np.sum(perf * perf, axis=1))
    near = dist < np.maximum(EPS_FRACTION * length, 1e-12)[:, None]
    core = near.sum(axis=1) >= COUNT_THRESHOLD
    label = np.full(m, -1)
    n_clusters = 0
    for start in range(m):
        if label[start] >= 0 or not core[start]:
            continue
        label[start] = n_clusters
        todo = [start]
        while todo:
            p = todo.pop()
            for q in np.flatnonzero(near[p] & (label < 0)):
                label[q] = n_clusters
                if core[q]:
                    todo.append(q)
        n_clusters += 1
    for r in np.flatnonzero(label < 0):      # isolated ranks: one each
        label[r] = n_clusters
        n_clusters += 1
    sizes = np.bincount(label, minlength=n_clusters)
    majority = int(np.argmax(sizes))
    stragglers = [int(r) for r in np.flatnonzero(label != majority)]
    shortest = float(length.min()) or float(length.mean()) or 1.0
    return {"stragglers": stragglers, "severity": float(dist.max()) / shortest}
