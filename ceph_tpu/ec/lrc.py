"""LRC: layered locally-repairable codes.

Behavioral mirror of reference src/erasure-code/lrc/ErasureCodeLrc.{h,cc}:
a stack of layers, each a chunk-subset delegation to another EC plugin
(struct Layer, ErasureCodeLrc.h:51-61), profile either explicit
mapping+layers JSON or generated from (k, m, l) (parse_kml,
ErasureCodeLrc.cc:295), locality-aware minimum_to_decode
(ErasureCodeLrc.cc:572) so a single erasure reads only its local group,
and multi-step CRUSH rule generation (rule_steps, ErasureCodeLrc.h:66-75,
create_rule ErasureCodeLrc.cc).

The compute stays on the TPU: every layer delegates to a MatrixCodec whose
encode/decode is the MXU bit-matrix matmul — LRC itself only routes chunk
subsets, exactly like the reference routes bufferlists between plugins.
"""

from __future__ import annotations

import errno
import itertools
import json
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Set, Tuple

import numpy as np

from ceph_tpu.ec.codec import MatrixCodec, _DeviceMatrixEngine, bytewise_engine
from ceph_tpu.ec.interface import ECError, ErasureCodeInterface, ErasureCodeProfile
from ceph_tpu.ec.table_cache import DecodeTableCache

DEFAULT_KML = "-1"


@dataclass
class Layer:
    """One LRC layer (reference ErasureCodeLrc.h:51-61)."""

    chunks_map: str
    profile: ErasureCodeProfile = field(default_factory=dict)
    erasure_code: ErasureCodeInterface = None
    data: List[int] = field(default_factory=list)
    coding: List[int] = field(default_factory=list)
    chunks: List[int] = field(default_factory=list)
    chunks_as_set: Set[int] = field(default_factory=set)


@dataclass
class Step:
    """One generated CRUSH rule step (reference ErasureCodeLrc.h:66-75)."""

    op: str
    type: str
    n: int


class _FlatLayersEngine(_DeviceMatrixEngine):
    """The layer walk as ONE matrix engine: the seam the plane entry
    points of ``ec/stripe.py`` drive (``codec.matrix_engine``).  The
    generator is the composed one (``_flat_coding_matrix``: every parity,
    global or local, over the data chunks), so encode is the base
    class's one matmul.  Decode is not a survivor-submatrix inversion:
    the four chunks of a local group have rank 3, so not every k rows
    invert, and fewer than k decode a chunk of their own group.
    ``decode_matrix`` composes the bottom-up layer walk over exactly the
    chunks it is given and RAISES where no layer can make progress."""

    def __init__(self, codec: "ErasureCodeLrc"):
        super().__init__(codec.data_chunk_count,
                         codec.chunk_count - codec.data_chunk_count,
                         codec._flat_coding_matrix())
        self._walk = codec._flat_decode_matrix

    def decode_matrix(self, src_rows: Tuple[int, ...],
                      out_rows: Tuple[int, ...]) -> np.ndarray:
        return self._walk(tuple(src_rows), tuple(out_rows))


class ErasureCodeLrc(MatrixCodec):
    def __init__(self):
        super().__init__()
        self.layers: List[Layer] = []
        self.chunk_count = 0
        self.data_chunk_count = 0
        self.rule_steps: List[Step] = [Step("chooseleaf", "host", 0)]
        # per erasure pattern: the composed pruned recovery, and per
        # (want, available): the sources chosen for it
        self._dec_jit: Dict = {}
        self._sources = DecodeTableCache()

    # -- profile parsing ----------------------------------------------------

    def _parse_kml(self, profile: ErasureCodeProfile) -> None:
        """Generate mapping/layers/rule-steps from (k, m, l)
        (reference parse_kml, ErasureCodeLrc.cc:295)."""
        k = self.to_int("k", profile, DEFAULT_KML)
        m = self.to_int("m", profile, DEFAULT_KML)
        l = self.to_int("l", profile, DEFAULT_KML)
        if k == -1 and m == -1 and l == -1:
            return
        if k == -1 or m == -1 or l == -1:
            raise ECError(errno.EINVAL,
                          "all of k, m, l must be set or none of them")
        for generated in ("mapping", "layers", "crush-steps"):
            if generated in profile and profile[generated]:
                raise ECError(
                    errno.EINVAL,
                    f"the {generated} parameter cannot be set when k, m, l are set")
        if (k + m) % l:
            raise ECError(errno.EINVAL, "k + m must be a multiple of l")
        local_group_count = (k + m) // l
        if k % local_group_count:
            raise ECError(errno.EINVAL, "k must be a multiple of (k + m) / l")
        if m % local_group_count:
            raise ECError(errno.EINVAL, "m must be a multiple of (k + m) / l")

        mapping = ""
        for _ in range(local_group_count):
            mapping += "D" * (k // local_group_count) + \
                "_" * (m // local_group_count) + "_"
        profile["mapping"] = mapping

        layers = [ ]
        # global layer
        desc = ""
        for _ in range(local_group_count):
            desc += "D" * (k // local_group_count) + \
                "c" * (m // local_group_count) + "_"
        layers.append([desc, ""])
        # local layers
        for i in range(local_group_count):
            desc = ""
            for j in range(local_group_count):
                if i == j:
                    desc += "D" * l + "c"
                else:
                    desc += "_" * (l + 1)
            layers.append([desc, ""])
        profile["layers"] = json.dumps(layers)

        rule_locality = profile.get("crush-locality", "")
        rule_failure_domain = profile.get("crush-failure-domain", "host")
        if rule_locality:
            self.rule_steps = [
                Step("choose", rule_locality, local_group_count),
                Step("chooseleaf", rule_failure_domain, l + 1),
            ]
        elif rule_failure_domain:
            self.rule_steps = [Step("chooseleaf", rule_failure_domain, 0)]

    def _parse_rule(self, profile: ErasureCodeProfile) -> None:
        """crush-steps JSON override (reference parse_rule)."""
        if not profile.get("crush-steps"):
            return
        try:
            description = json.loads(profile["crush-steps"])
        except json.JSONDecodeError as e:
            raise ECError(errno.EINVAL, f"failed to parse crush-steps: {e}")
        if not isinstance(description, list):
            raise ECError(errno.EINVAL, "crush-steps must be a JSON array")
        self.rule_steps = []
        for entry in description:
            if not isinstance(entry, list):
                raise ECError(errno.EINVAL,
                              "each crush-steps element must be a JSON array")
            op, type_, n = "", "", 0
            for pos, v in enumerate(entry):
                if pos in (0, 1) and not isinstance(v, str):
                    raise ECError(errno.EINVAL,
                                  f"crush-steps element {pos} must be a string")
                if pos == 2 and not isinstance(v, int):
                    raise ECError(errno.EINVAL,
                                  "crush-steps element 2 must be an int")
                if pos == 0:
                    op = v
                elif pos == 1:
                    type_ = v
                elif pos == 2:
                    n = v
            self.rule_steps.append(Step(op, type_, n))

    def _layers_parse(self, profile: ErasureCodeProfile) -> None:
        """layers JSON -> Layer list (reference layers_parse,
        ErasureCodeLrc.cc:145)."""
        if not profile.get("layers"):
            raise ECError(errno.EINVAL, "could not find 'layers' in profile")
        try:
            description = json.loads(profile["layers"])
        except json.JSONDecodeError as e:
            raise ECError(errno.EINVAL, f"failed to parse layers: {e}")
        if not isinstance(description, list):
            raise ECError(errno.EINVAL, "layers must be a JSON array")
        self.layers = []
        for position, entry in enumerate(description):
            if not isinstance(entry, list):
                raise ECError(
                    errno.EINVAL,
                    f"layers element at position {position} must be a JSON array")
            if not entry or not isinstance(entry[0], str):
                raise ECError(
                    errno.EINVAL,
                    f"the first element of layers entry {position} must be a string")
            layer = Layer(chunks_map=entry[0])
            if len(entry) > 1:
                config = entry[1]
                if isinstance(config, str):
                    if config:
                        try:
                            layer.profile = {
                                str(a): str(b)
                                for a, b in json.loads(config).items()
                            }
                        except (json.JSONDecodeError, AttributeError) as e:
                            raise ECError(errno.EINVAL,
                                          f"bad layer config {config!r}: {e}")
                elif isinstance(config, dict):
                    layer.profile = {str(a): str(b) for a, b in config.items()}
                else:
                    raise ECError(
                        errno.EINVAL,
                        f"the second element of layers entry {position} "
                        "must be a string or object")
            # trailing elements ignored, like the reference
            self.layers.append(layer)

    def _layers_init(self) -> None:
        """Resolve chunk positions + instantiate per-layer codecs
        (reference layers_init, ErasureCodeLrc.cc:215)."""
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry

        registry = ErasureCodePluginRegistry.instance()
        for layer in self.layers:
            layer.data = [i for i, c in enumerate(layer.chunks_map) if c == "D"]
            layer.coding = [i for i, c in enumerate(layer.chunks_map) if c == "c"]
            layer.chunks = layer.data + layer.coding
            layer.chunks_as_set = set(layer.chunks)
            layer.profile.setdefault("k", str(len(layer.data)))
            layer.profile.setdefault("m", str(len(layer.coding)))
            layer.profile.setdefault("plugin", "jerasure")
            layer.profile.setdefault("technique", "reed_sol_van")
            layer.erasure_code = registry.factory(
                layer.profile["plugin"], layer.profile)

    def _layers_sanity_checks(self) -> None:
        if len(self.layers) < 1:
            raise ECError(errno.EINVAL, "layers must have at least one entry")
        for position, layer in enumerate(self.layers):
            if len(layer.chunks_map) != self.chunk_count:
                raise ECError(
                    errno.EINVAL,
                    f"layer {position} chunks_map {layer.chunks_map!r} must be "
                    f"{self.chunk_count} characters long")

    def init(self, profile: ErasureCodeProfile) -> None:
        # ordering mirrors reference ErasureCodeLrc::init (:496-553)
        self._parse_kml(profile)
        self.rule_root = self.to_string("crush-root", profile, "default")
        self.rule_failure_domain = self.to_string(
            "crush-failure-domain", profile, "host")
        self.rule_device_class = self.to_string("crush-device-class", profile, "")
        self._parse_rule(profile)
        self._layers_parse(profile)
        self._layers_init()
        if not profile.get("mapping"):
            raise ECError(errno.EINVAL, "the 'mapping' profile is missing")
        mapping = profile["mapping"]
        self.data_chunk_count = mapping.count("D")
        self.chunk_count = len(mapping)
        self._layers_sanity_checks()
        self.to_mapping(profile)
        self.k = self.data_chunk_count
        self.m = self.chunk_count - self.data_chunk_count
        # the batch and plane paths multiply by the flattened layers,
        # which are matrices only where every layer is a bytewise
        # GF(2^8) matrix code; any other stack keeps the scalar walk
        if all(bytewise_engine(layer.erasure_code) is not None
               for layer in self.layers):
            self.engine = _FlatLayersEngine(self)
        # kml-generated parameters are internal; do not expose them
        # (reference :545-550)
        if profile.get("l") and profile["l"] != DEFAULT_KML:
            profile.pop("mapping", None)
            profile.pop("layers", None)
        self._profile = profile

    # -- geometry -----------------------------------------------------------

    def get_chunk_count(self) -> int:
        return self.chunk_count

    def get_data_chunk_count(self) -> int:
        return self.data_chunk_count

    def get_chunk_size(self, object_size: int) -> int:
        return self.layers[0].erasure_code.get_chunk_size(object_size)

    # -- minimum_to_decode (the locality win) -------------------------------

    def minimum_to_decode(
        self, want_to_read: Set[int], available_chunks: Set[int]
    ) -> Set[int]:
        """Reference ErasureCodeLrc::minimum_to_decode (:572): recover
        erasures with as few chunks as possible, preferring the lowest
        (most local) layers; on a single local erasure the read set is the
        local group, not k chunks."""
        erasures_total = set()
        erasures_not_recovered = set()
        erasures_want = set()
        for i in range(self.get_chunk_count()):
            if i not in available_chunks:
                erasures_total.add(i)
                erasures_not_recovered.add(i)
                if i in want_to_read:
                    erasures_want.add(i)

        # Case 1: nothing wanted is missing
        if not erasures_want:
            return set(want_to_read)

        # Case 2: recover wanted erasures bottom-up (local layers last in
        # the list, reverse iteration visits them first)
        minimum: Set[int] = set()
        for layer in reversed(self.layers):
            layer_want = want_to_read & layer.chunks_as_set
            if not layer_want:
                continue
            layer_erasures = layer_want & erasures_want
            if not layer_erasures:
                minimum |= layer_want
                continue
            erasures = layer.chunks_as_set & erasures_not_recovered
            if len(erasures) > layer.erasure_code.get_coding_chunk_count():
                # too many erasures for this layer: hope an upper layer helps
                continue
            layer_minimum = layer.chunks_as_set - erasures_not_recovered
            for j in erasures:
                erasures_not_recovered.discard(j)
                erasures_want.discard(j)
            minimum |= layer_minimum
        if not erasures_want:
            minimum |= want_to_read
            minimum -= erasures_total
            return minimum

        # Case 3: recover everything recoverable, layer by layer, and read
        # all available chunks
        erasures_total = {
            i for i in range(self.get_chunk_count()) if i not in available_chunks
        }
        for layer in reversed(self.layers):
            layer_erasures = layer.chunks_as_set & erasures_total
            if not layer_erasures:
                continue
            if len(layer_erasures) <= layer.erasure_code.get_coding_chunk_count():
                erasures_total -= layer_erasures
        if not erasures_total:
            return set(available_chunks)

        raise ECError(errno.EIO,
                      f"not enough chunks in {sorted(available_chunks)} "
                      f"to read {sorted(want_to_read)}")

    def decode_sources(self, want, available) -> Optional[List[int]]:
        """Which of the logical chunks ``available`` (those that came) a
        decode of the logical chunks ``want`` should multiply;
        ECError(EIO) where the layers cannot produce ``want`` from
        them.  Not every k chunks of this code will do (a local group
        of four has rank 3), so this code always has an answer
        (``ErasureCode.decode_sources``): the first k of ``available``,
        in its order, that the layer walk decodes ``want`` from — an
        MDS code's rule wherever that set decodes — and all of
        ``available`` where no k of them do and the walk over all still
        gets there."""
        available = list(available)
        key = (frozenset(want), tuple(available))
        chosen = self._sources.get(key)
        if chosen is None:
            chosen = next(
                (list(c) for c in itertools.combinations(available, self.k)
                 if self._decodable(c, want)), None)
            if chosen is None:
                if not self._decodable(available, want):
                    raise ECError(
                        errno.EIO, f"{sorted(available)} do not decode "
                        f"{sorted(want)}")
                chosen = available
            self._sources.put(key, chosen)
        return list(chosen)

    def _decodable(self, src, want) -> bool:
        """Can the layers produce ``want`` from exactly ``src``?"""
        src = set(src)
        try:
            self._decode_plan(
                tuple(c for c in range(self.chunk_count) if c not in src),
                tuple(c for c in want if c not in src))
        except ECError:
            return False
        return True

    # -- encode / decode ----------------------------------------------------

    def encode_chunks(self, chunks: Dict[int, np.ndarray]) -> None:
        """Apply every layer in order: the global layer fills the global
        parities, then each local layer its local parity (reference
        encode_chunks, ErasureCodeLrc.cc:744 with want = all chunks)."""
        for layer in self.layers:
            layer_chunks = {
                j: chunks[c] for j, c in enumerate(layer.chunks)
            }
            layer.erasure_code.encode_chunks(layer_chunks)

    def decode_chunks(
        self,
        want_to_read: Set[int],
        chunks: Mapping[int, np.ndarray],
        decoded: Dict[int, np.ndarray],
    ) -> None:
        """Reference decode_chunks (ErasureCodeLrc.cc:782): walk layers
        bottom-up; each successful layer decode improves ``decoded`` and
        shrinks the erasure set for the layers above."""
        erasures = {
            i for i in range(self.get_chunk_count()) if i not in chunks
        }
        want_to_read_erasures = erasures & want_to_read
        for layer in reversed(self.layers):
            layer_erasures = layer.chunks_as_set & erasures
            if len(layer_erasures) > layer.erasure_code.get_coding_chunk_count():
                continue  # too many erasures for this layer
            if not layer_erasures:
                continue  # all of this layer's chunks already available
            layer_want: Set[int] = set()
            layer_chunks: Dict[int, np.ndarray] = {}
            layer_decoded: Dict[int, np.ndarray] = {}
            for j, c in enumerate(layer.chunks):
                # pick from `decoded` (not `chunks`) to reuse chunks
                # recovered by previous layers
                if c not in erasures:
                    layer_chunks[j] = decoded[c]
                if c in want_to_read:
                    layer_want.add(j)
                layer_decoded[j] = decoded[c]
            layer.erasure_code.decode_chunks(
                layer_want, layer_chunks, layer_decoded)
            for j, c in enumerate(layer.chunks):
                decoded[c][...] = layer_decoded[j]
                erasures.discard(c)
            want_to_read_erasures = erasures & want_to_read
            if not want_to_read_erasures:
                break
        if want_to_read_erasures:
            raise ECError(errno.EIO,
                          f"unable to read {sorted(want_to_read_erasures)}")

    # -- batched device paths -----------------------------------------------
    #
    # The cluster stripe layer (ceph_tpu.ec.stripe) talks in LOGICAL chunk
    # ids: data chunks 0..k-1 then coding chunks k..n-1, the same order
    # chunk_index() maps to positions.  Layers think in POSITIONS (indices
    # into the mapping string), so the batch paths convert at the boundary.

    def _positions(self):
        data_pos = self.chunk_mapping[: self.data_chunk_count]
        coding_pos = self.chunk_mapping[self.data_chunk_count:]
        return data_pos, coding_pos

    def _flat_coding_matrix(self) -> "np.ndarray":
        """Compose the layer walk into ONE (m_total, k) GF(2^8) matrix
        over the logical data chunks (round 5).

        Every LRC parity — global or local — is a linear function of the
        data (local layers that read global parities compose through
        them), so the whole layered encode collapses to a single MXU
        matmul.  encode_chunks keeps the literal layer walk (it IS the
        reference semantics the goldens pin); this matrix is
        algebraically identical by construction."""
        from ceph_tpu.ops import gf8

        k = self.data_chunk_count
        data_pos, coding_pos = self._positions()
        expr = {c: np.zeros(k, dtype=np.uint8) for c in
                range(self.chunk_count)}
        for i, c in enumerate(data_pos):
            expr[c][i] = 1
        for layer in self.layers:
            lm = layer.erasure_code.engine.coding  # (lm, lk) bytes
            for r, cout in enumerate(layer.coding):
                acc = np.zeros(k, dtype=np.uint8)
                for j, cin in enumerate(layer.data):
                    coef = int(lm[r, j])
                    if coef:
                        acc ^= gf8.gf_mul(coef, expr[cin])
                expr[cout] = acc
        return np.stack([expr[c] for c in coding_pos])

    def planar_supported(self, chunk_size: int) -> bool:
        return self.engine is not None and \
            super().planar_supported(chunk_size)

    def stripe_unit(self, default: int) -> int:
        """The smallest unit >= ``default`` that every layer's chunk
        layout accepts (a packet-interleaved layer needs multiples of
        its w * packetsize)."""
        unit = super().stripe_unit(default)
        while True:
            nxt = max(layer.erasure_code.stripe_unit(unit)
                      for layer in self.layers)
            if nxt == unit:
                return unit
            unit = nxt

    def encode_batch(self, data):
        """(B, k, S) logical data -> (B, m, S) coding chunks,
        device-resident, as ONE flattened-generator MXU matmul.  A stack
        with a layer that is no bytewise GF(2^8) matrix code has no such
        generator: its batch is the literal layer walk over the shard
        rows (every layout a layer has is local to a chunk, so the walk
        over the stripes' chunks concatenated is the walk stripe by
        stripe), byte-at-rest."""
        if self.engine is not None:
            return self.engine.encode_parity_batch(data)
        data = np.asarray(data, dtype=np.uint8)
        b, k, s = data.shape
        data_pos, coding_pos = self._positions()
        rows = data.transpose(1, 0, 2).reshape(k, b * s)
        chunks = {p: np.zeros(b * s, dtype=np.uint8) for p in coding_pos}
        chunks.update((p, rows[j].copy()) for j, p in enumerate(data_pos))
        self.encode_chunks(chunks)
        return np.stack([chunks[p] for p in coding_pos]) \
            .reshape(len(coding_pos), b, s).transpose(1, 0, 2)

    def decode_batch(self, erasures, chunks, want=None):
        """Batched single-pattern reconstruction.  ``chunks``: (B, n, S)
        in logical order with zeros at erased ids; ``erasures`` = every
        unavailable logical id; ``want`` = subset to return (default
        all).  Returns (B, len(want), S): the composed layer walk as one
        gather + matmul, plans cached like the reference's decode
        tables; the literal walk for a stack that has no flat engine."""
        import jax

        from ceph_tpu.ec.codec import _gather_encode_batch_jit

        want = tuple(erasures if want is None else want)
        if self.engine is not None:
            bitmat, src_ids = self._planar_decode_plan(tuple(erasures), want)
            return _gather_encode_batch_jit(
                bitmat, jax.numpy.asarray(chunks), src_ids)
        chunks = np.asarray(chunks, dtype=np.uint8)
        b, n, s = chunks.shape
        pos = self.chunk_mapping
        rows = chunks.transpose(1, 0, 2).reshape(n, b * s)
        decoded = {pos[e]: rows[e].copy() for e in range(n)}
        have = {pos[e]: decoded[pos[e]] for e in range(n)
                if e not in erasures}
        self.decode_chunks({pos[e] for e in want}, have, decoded)
        return np.stack([decoded[pos[e]] for e in want]) \
            .reshape(len(want), b, s).transpose(1, 0, 2)

    def _planar_decode_plan(self, erasures, want):
        """(recovery bit-matrix, source chunk ids) for one erasure
        pattern: the walk composed over every available chunk, then
        pruned to the chunks it uses (round 6, locality: a single local
        erasure pulls its group of l, not all n-1 survivors — the
        reference's minimum_to_decode read set, ErasureCodeLrc.cc:572,
        applied to the batched matmul; coefficients are untouched, only
        the source set shrinks)."""
        from ceph_tpu.ops import gf8

        key = (erasures, want)
        cached = self._dec_jit.get(key)
        if cached is None:
            avail = tuple(e for e in range(self.chunk_count)
                          if e not in erasures)
            flat = self._flat_decode_matrix(avail, want)
            used = np.flatnonzero(flat.any(axis=0))
            if used.size == 0:
                used = np.arange(min(1, len(avail)))
            cached = self._dec_jit[key] = (
                gf8.expand_bitmatrix(np.ascontiguousarray(flat[:, used])),
                tuple(avail[int(i)] for i in used))
        return cached

    def _flat_decode_matrix(self, src: Tuple[int, ...],
                            want: Tuple[int, ...]) -> np.ndarray:
        """Compose the bottom-up layer walk that ``decode_chunks`` makes
        with exactly the logical chunks ``src`` available into ONE
        (len(want), len(src)) GF(2^8) recovery matrix (the walk is
        linear, so its per-step tiny-K matmuls collapse).  Raises
        ECError(EIO) where the walk cannot reach ``want``: a source set
        of too low a rank is refused here, never decoded."""
        from ceph_tpu.ops import gf8

        steps, out_pos = self._decode_plan(
            tuple(e for e in range(self.chunk_count) if e not in src),
            tuple(want))
        logical_to_pos = list(self.chunk_mapping)
        expr: dict = {}
        for i, e in enumerate(src):
            row = np.zeros(len(src), dtype=np.uint8)
            row[i] = 1
            expr[logical_to_pos[e]] = row
        for layer, local_erasures, _layer_erased in steps:
            lsrc = self._layer_src(layer, local_erasures)
            rmat = layer.erasure_code.engine.decode_matrix(
                lsrc, local_erasures)              # (out, src) bytes
            for r, out_local in enumerate(local_erasures):
                acc = np.zeros(len(src), dtype=np.uint8)
                for j, s_local in enumerate(lsrc):
                    coef = int(rmat[r, j])
                    if coef:
                        acc ^= gf8.gf_mul(coef,
                                          expr[layer.chunks[s_local]])
                expr[layer.chunks[out_local]] = acc
        return np.stack([expr[p] for p in out_pos])

    @staticmethod
    def _layer_src(layer, local_erasures):
        ln = len(layer.chunks)
        lk = layer.erasure_code.get_data_chunk_count()
        avail = tuple(i for i in range(ln) if i not in local_erasures)
        return avail[:lk]

    def _decode_plan(self, erasures, want):
        """Host-side routing decisions for one erasure pattern: which
        layers run, with which local erasures."""
        logical_to_pos = list(self.chunk_mapping)
        erased_pos = {logical_to_pos[e] for e in erasures}
        want_pos = {logical_to_pos[e] for e in want}
        steps = []
        for layer in reversed(self.layers):
            layer_erased = [c for c in layer.chunks if c in erased_pos]
            if not layer_erased:
                continue
            if len(layer_erased) > layer.erasure_code.get_coding_chunk_count():
                continue
            local_ids = {c: j for j, c in enumerate(layer.chunks)}
            steps.append(
                (layer, tuple(local_ids[c] for c in layer_erased),
                 tuple(layer_erased)))
            erased_pos -= set(layer_erased)
            if not erased_pos & want_pos:
                break
        if erased_pos & want_pos:
            raise ECError(
                errno.EIO,
                f"unable to reconstruct positions {sorted(erased_pos & want_pos)}")
        out_pos = tuple(logical_to_pos[e] for e in want)
        return steps, out_pos

    # -- CRUSH rule generation ----------------------------------------------

    def create_rule(self, name: str, cmap) -> int:
        """Generate the multi-step indep rule (reference create_rule):
        SET_CHOOSELEAF_TRIES 5, SET_CHOOSE_TRIES 100, TAKE root, then one
        CHOOSE/CHOOSELEAF_INDEP per rule_step, then EMIT."""
        from ceph_tpu.crush import types as ct

        root = None
        for item_id, item_name in cmap.item_names.items():
            if item_name == self.rule_root:
                root = item_id
                break
        if root is None:
            raise ECError(errno.ENOENT,
                          f"root item {self.rule_root} does not exist")
        type_ids = {v: k for k, v in cmap.type_names.items()}
        steps = [
            (ct.RULE_SET_CHOOSELEAF_TRIES, 5, 0),
            (ct.RULE_SET_CHOOSE_TRIES, 100, 0),
            (ct.RULE_TAKE, root, 0),
        ]
        for s in self.rule_steps:
            op = (ct.RULE_CHOOSELEAF_INDEP if s.op == "chooseleaf"
                  else ct.RULE_CHOOSE_INDEP)
            if s.type not in type_ids:
                raise ECError(errno.EINVAL, f"unknown crush type {s.type}")
            steps.append((op, s.n, type_ids[s.type]))
        steps.append((ct.RULE_EMIT, 0, 0))
        return cmap.add_rule(
            ct.Rule(steps=steps, type=3, min_size=3,
                    max_size=self.get_chunk_count()))


def make_lrc(profile: ErasureCodeProfile):
    codec = ErasureCodeLrc()
    codec.init(profile)
    return codec
