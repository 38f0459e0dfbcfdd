"""Roofline-term extraction from a compiled (unexecuted) XLA artifact.

Three terms per (arch x shape x mesh) cell, from the target chip's peaks
(``PEAKS``, keyed by ``device_kind``):

  compute    = HLO_FLOPs_global    / (chips * 197e12 FLOP/s bf16)
  memory     = HLO_bytes_global    / (chips * 819e9 B/s HBM)
  collective = collective_bytes    / (chips * 4 * 50e9 B/s ICI links)

``cost_analysis()`` reports the PER-DEVICE partitioned module (SPMD = one
program per device), so globals are per-device * chips and the chip count
cancels; we keep both forms for the table.  Collective bytes are NOT in
cost_analysis — we parse the optimized HLO and sum, for every
all-gather/all-reduce/reduce-scatter/all-to-all/collective-permute, the
bytes that cross the wire per device (receive-volume convention: result
bytes for gather-like ops, operand bytes for reduce-scatter; all-reduce
counts 2x operand (reduce-scatter + all-gather of a ring)).
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

__all__ = ["PEAKS", "peaks", "HW", "collective_bytes", "roofline_terms", "RooflineReport",
           "model_flops", "classify_tile_rows", "KernelLaunchSpec",
           "launch_spec", "spec_candidates"]

# Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud TPU documentation, "TPU v5e" system architecture (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over four
# links); 16 MiB is Mosaic's default scoped-VMEM limit on that chip.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,       # bf16
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 50e9,             # B/s per link
        "ici_links": 4,             # links/chip on a 2-D torus (16x16 pod)
        "hbm_bytes": 16 * 2**30,    # capacity
        "vmem_bytes": 16 * 2**20,   # scoped VMEM per core — the Pallas tile budget
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of one chip kind; a kind the table lacks is an
    error, never a default.

    >>> peaks("TPU v5 lite")["hbm_bw"]
    819000000000.0
    """
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


# The chip this repo's kernels and dry-run model target: the tile budget
# below and ``roofline_terms`` read its peaks.
TARGET_KIND = "TPU v5 lite"
HW = peaks(TARGET_KIND)

# unified kernel-launch model: lanes per VPU row, the VMEM fraction a
# double-buffered kernel may claim for one grid step, and the largest row
# count worth scheduling (past it the grid has too few steps to pipeline).
_CLASSIFY_LANES = 128
_CLASSIFY_VMEM_FRACTION = 3   # 1/3: input double-buffer + in-flight outputs
_CLASSIFY_MAX_ROWS = 128


@dataclass(frozen=True)
class KernelLaunchSpec:
    """One launch contract shared by every sort kernel (DESIGN.md §10).

    Each Pallas sort kernel used to pick its own tile shape with its own
    ad-hoc constant (classify: roofline rows, dispatch_rank: ``rows=8``,
    merge_path: ``tile=256``).  A :class:`KernelLaunchSpec` replaces the
    three code paths with one derivation: ``kind`` names the kernel's
    per-row working-set model, ``rows`` x ``lanes`` is the grid-step tile,
    ``vmem_budget`` is the bytes one grid step may claim (the VMEM budget
    already divided by ``double_buffer`` in-flight copies), and
    ``interpret`` is the shared off-TPU policy (``None`` resolves through
    ``kernels.resolve_interpret``).  ``rows == 0`` means no candidate tile
    divides the requested ``n`` — callers then stay on their XLA path.
    """

    kind: str
    rows: int
    lanes: int = _CLASSIFY_LANES
    vmem_budget: int = HW["vmem_bytes"] // _CLASSIFY_VMEM_FRACTION
    double_buffer: int = 2
    interpret: Optional[bool] = None

    @property
    def tile(self) -> int:
        """Elements per grid step."""
        return self.rows * self.lanes

    def resolve_interpret(self) -> bool:
        from repro.kernels import resolve_interpret

        return resolve_interpret(self.interpret)


def _bytes_per_row(kind: str, key_bytes: int, k: Optional[int]) -> int:
    """VMEM bytes one tile row of 128 lanes costs in kernel ``kind``.

    The models count the resident operands plus the dominant broadcast
    intermediate of each kernel body:

      classify     keys + (lanes, 2k) int32 compare/one-hot + bucket out
      rank         int32 bucket ids + (lanes, nb) one-hot + rank/dest out
      level_fused  classify AND rank in one body: keys + one-hot against
                   nb = 2k+1 + bucket/rank outputs
      merge        two (key, int32 src) sequences of the double window
      permute      two swap buffers of block rows
    """
    L = _CLASSIFY_LANES
    if kind == "classify":
        return L * (key_bytes + 4 * (2 * k) + 4)
    if kind == "rank":
        return L * (4 + 4 * k + 4)          # k is nb here
    if kind == "level_fused":
        return L * (key_bytes + 4 * (2 * k + 1) + 8)
    if kind == "merge":
        return L * 4 * (key_bytes + 4)       # (key, src) x in/out staging
    if kind == "permute":
        return L * 2 * key_bytes             # the two swap buffers
    raise ValueError(f"unknown kernel kind {kind!r}")


_MAX_ROWS = {
    "classify": _CLASSIFY_MAX_ROWS,
    "rank": _CLASSIFY_MAX_ROWS,
    "level_fused": _CLASSIFY_MAX_ROWS,
    "merge": 8,       # merge-path T = rows*128; diagonals grow linearly in T
    "permute": 64,    # block_elems = rows*128
}


def spec_candidates(
    kind: str,
    key_bytes: int,
    k: Optional[int] = None,
    *,
    vmem_bytes: Optional[int] = None,
    max_rows: Optional[int] = None,
) -> tuple:
    """Descending power-of-two row-count candidates for kernel ``kind``.

    The largest candidate is the biggest power of two whose working set
    (``_bytes_per_row`` x rows) fits the per-step VMEM budget (one
    ``_CLASSIFY_VMEM_FRACTION``-th of VMEM: input double-buffer plus
    in-flight outputs); the tail enumerates down to one row so callers can
    pick the largest candidate dividing their n and the plan cache can
    sweep the leading entries.
    """
    budget = (HW["vmem_bytes"] if vmem_bytes is None else vmem_bytes)
    budget //= _CLASSIFY_VMEM_FRACTION
    per_row = _bytes_per_row(kind, key_bytes, k)
    cap = _MAX_ROWS[kind] if max_rows is None else max_rows
    rows = 1
    while rows * 2 <= cap and (rows * 2) * per_row <= budget:
        rows *= 2
    out = []
    while rows >= 1:
        out.append(rows)
        rows //= 2
    return tuple(out)


def launch_spec(
    kind: str,
    key_bytes: int,
    k: Optional[int] = None,
    *,
    n: Optional[int] = None,
    rows: Optional[int] = None,
    vmem_bytes: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> KernelLaunchSpec:
    """The one tile-shape derivation every sort kernel launches through.

    ``rows`` pins a swept value (the plan-cache autotune dimension);
    otherwise the largest :func:`spec_candidates` entry wins, filtered to
    tiles dividing ``n`` when given (``rows == 0`` in the returned spec
    when none divides — n not 128-aligned — and the caller stays on XLA).

    >>> launch_spec("classify", 4, 128).rows
    32
    >>> launch_spec("merge", 4).tile
    1024
    >>> launch_spec("classify", 4, 128, n=1000).rows
    0
    """
    budget = (HW["vmem_bytes"] if vmem_bytes is None else vmem_bytes)
    budget //= _CLASSIFY_VMEM_FRACTION
    cands = spec_candidates(kind, key_bytes, k, vmem_bytes=vmem_bytes)
    if rows is None:
        rows = 0
        for cand in cands:
            if n is None or n % (cand * _CLASSIFY_LANES) == 0:
                rows = cand
                break
    elif n is not None and n % (rows * _CLASSIFY_LANES):
        rows = 0
    from repro import obs  # lazy: keep the roofline importable without jax

    obs.count("launch.spec", kind=kind, rows=rows)  # rows=0 = XLA fallback
    return KernelLaunchSpec(
        kind=kind, rows=rows, vmem_budget=budget, interpret=interpret
    )


def classify_tile_rows(
    key_bytes: int,
    k: int,
    *,
    vmem_bytes: Optional[int] = None,
    max_rows: int = _CLASSIFY_MAX_ROWS,
) -> tuple:
    """Row-count candidates for the fused classify kernels, from the VMEM
    roofline instead of a hard-coded constant.

    One grid step of ``kernels/classify.py`` holds, per tile row of 128
    lanes: the keys (``key_bytes`` each), the int32 one-hot / compare
    broadcast against nb = 2k buckets, and the int32 bucket output — so

        bytes_per_row = 128 * (key_bytes + 4 * 2k + 4)

    and the largest power-of-two row count fitting a third of VMEM
    (input double-buffer + in-flight outputs) leads a descending
    candidate tuple; the plan cache sweeps the leading entries and the
    level pass picks the largest candidate dividing n.  At the defaults
    (f32/u32 keys, k = 128, 16 MiB VMEM) this reproduces the previously
    hard-coded 32 rows.  This is the ``kind="classify"`` projection of
    :func:`spec_candidates`, kept as the stable entry point.

    >>> classify_tile_rows(4, 128)[0]
    32
    >>> classify_tile_rows(4, 32)[0] > classify_tile_rows(8, 256)[0]
    True
    """
    return spec_candidates(
        "classify", key_bytes, k, vmem_bytes=vmem_bytes, max_rows=max_rows
    )

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, incl. tuples '(f32[..], bf16[..])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device wire bytes by collective kind, from optimized HLO text."""
    out: Dict[str, int] = {}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        result_shape, kind = m.group(1), m.group(2)
        # async pairs: count the -start, skip the -done
        if "-done(" in line:
            continue
        rb = _shape_bytes(result_shape)
        # operand bytes: everything inside the call parens
        inner = line[line.index("(") + 1 :]
        ob = _shape_bytes(inner)
        if kind == "all-reduce":
            wire = 2 * ob          # ring RS+AG
        elif kind == "reduce-scatter":
            wire = ob
        elif kind == "all-gather":
            wire = rb
        elif kind == "all-to-all":
            wire = max(rb, ob)
        else:  # collective-permute
            wire = rb
        out[kind] = out.get(kind, 0) + wire
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    coll_breakdown: Dict[str, int]
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / global HLO flops
    peak_mem_per_dev: Optional[float] = None
    note: str = ""
    raw_flops_per_dev: float = 0.0   # cost_analysis() as reported (loops x1)
    raw_bytes_per_dev: float = 0.0
    n_while: int = 0
    loop_trips: Dict[str, int] = field(default_factory=dict)
    bytes_min_per_dev: float = 0.0   # fusion-optimistic HBM traffic
    t_memory_min: float = 0.0
    bottleneck_min: str = ""         # bottleneck under optimistic memory

    def to_json(self) -> str:
        return json.dumps(asdict(self))


def roofline_terms(
    *, arch: str, shape: str, mesh_name: str, chips: int,
    flops_per_dev: float, bytes_per_dev: float, hlo_text: str,
    model_fl: float, peak_mem: Optional[float] = None, note: str = "",
) -> RooflineReport:
    """``flops_per_dev``/``bytes_per_dev`` are the RAW cost_analysis numbers
    (loop bodies counted once — see launch/hlo_cost.py).  We re-derive
    trip-count-corrected values from the HLO text and use THOSE for the
    three terms; the raws are kept in the report for comparison.  The
    peaks are ``HW``, the ``TARGET_KIND`` chip's."""
    from repro.launch.hlo_cost import analyze_hlo

    hc = analyze_hlo(hlo_text)
    raw_flops, raw_bytes = flops_per_dev, bytes_per_dev
    # corrected flops: never less than what XLA itself counted
    flops_per_dev = max(hc.flops, raw_flops)
    bytes_per_dev = max(hc.bytes, raw_bytes)
    bytes_min = hc.bytes_min
    coll = {k: int(v) for k, v in hc.coll.items()}
    cb = float(sum(coll.values()))
    t_c = flops_per_dev / HW["peak_flops"]
    t_m = bytes_per_dev / HW["hbm_bw"]          # conservative (XLA convention)
    t_m_min = bytes_min / HW["hbm_bw"]          # fusion-optimistic (TPU real)
    t_x = cb / (HW["ici_links"] * HW["ici_bw"])
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bott = max(terms, key=terms.get)
    # bottleneck under the TPU-realistic memory model (used by §Perf)
    terms_min = {"compute": t_c, "memory": t_m_min, "collective": t_x}
    bott_min = max(terms_min, key=terms_min.get)
    global_flops = flops_per_dev * chips
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_dev=flops_per_dev, bytes_per_dev=bytes_per_dev,
        coll_bytes_per_dev=cb, coll_breakdown=coll,
        t_compute=t_c, t_memory=t_m, t_collective=t_x, bottleneck=bott,
        model_flops=model_fl,
        useful_ratio=(model_fl / global_flops) if global_flops else 0.0,
        peak_mem_per_dev=peak_mem, note=note,
        raw_flops_per_dev=raw_flops, raw_bytes_per_dev=raw_bytes,
        n_while=hc.n_while, loop_trips=dict(hc.trips),
        bytes_min_per_dev=bytes_min, t_memory_min=t_m_min,
        bottleneck_min=bott_min,
    )


def _param_count(cfg) -> float:
    """Total parameter count N (all experts counted; N_active separately)."""
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    hd = cfg.hd
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "ssm":  # rwkv6
        tm = 5 * d * d + 2 * d * 64 + d  # r,k,v,g,o + lora
        cm = d * cfg.d_ff * 2 + d * d
        return L * (tm + cm) + emb
    attn = d * (cfg.num_heads * hd) * 2 + d * (cfg.num_kv_heads * hd) * 2
    if cfg.family == "moe":
        m = cfg.moe
        routed = m.num_experts * 3 * d * m.d_ff_expert
        shared = (3 * d * m.d_ff_shared) if m.num_shared else 0
        ffn = routed + shared + d * m.num_experts
    else:
        ffn = 3 * d * cfg.d_ff
    if cfg.family == "hybrid":
        s = cfg.ssm
        d_in = s.expand * d
        mamba = d * (2 * d_in + 2 * s.d_state + d_in // s.head_dim) + d_in * d
        per = mamba + 3 * d * cfg.d_ff
        groups = L // s.attn_every
        return L * per + attn + emb  # ONE shared attn block
    return L * (attn + ffn) + emb


def _active_param_count(cfg) -> float:
    if cfg.family != "moe":
        return _param_count(cfg)
    d, L = cfg.d_model, cfg.num_layers
    m = cfg.moe
    attn = d * (cfg.num_heads * cfg.hd) * 2 + d * (cfg.num_kv_heads * cfg.hd) * 2
    act = m.top_k * 3 * d * m.d_ff_expert + (3 * d * m.d_ff_shared if m.num_shared else 0)
    emb = cfg.vocab_size * d * 2
    return L * (attn + act + d * m.num_experts) + emb


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); D = tokens processed.
    For decode shapes D = global_batch (one token per request);
    train counts fwd+bwd (6ND), prefill/decode fwd only (2ND)."""
    n_act = _active_param_count(cfg)
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_act * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_act * toks
    return 2.0 * n_act * shape.global_batch
