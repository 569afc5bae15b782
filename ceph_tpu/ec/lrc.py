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
import json
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Set

import numpy as np

from ceph_tpu.ec.base import ErasureCode
from ceph_tpu.ec.interface import ECError, ErasureCodeInterface, ErasureCodeProfile

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


class ErasureCodeLrc(ErasureCode):
    def __init__(self):
        super().__init__()
        self.layers: List[Layer] = []
        self.chunk_count = 0
        self.data_chunk_count = 0
        self.rule_steps: List[Step] = [Step("chooseleaf", "host", 0)]
        # jitted batch entry points: the layer routing must be ONE device
        # dispatch — eager per-layer gathers/scatters cost a runtime round
        # trip each, which dominates end-to-end throughput
        self._enc_jit = None
        self._enc_planar_bitmat = None
        self._dec_jit: Dict = {}

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
        matmul.  The honest benchmark showed the per-layer walk paying
        tiny-K matmuls plus scatter materializations for 8.9 GB/s; the
        flattened matrix runs at the plain-RS rate.  encode_chunks keeps
        the literal layer walk (it IS the reference semantics the goldens
        pin); this matrix is algebraically identical by construction."""
        import numpy as np

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

    def encode_batch(self, data):
        """(B, k, S) logical data -> (B, m, S) coding chunks,
        device-resident, as ONE flattened-generator MXU matmul (see
        _flat_coding_matrix).

        The encode bit-matrix stays HOST numpy and is passed as a jit
        ARGUMENT: a jit must not close over a device array.
        """
        import jax

        from ceph_tpu.ops import gf8

        if self._enc_jit is None:
            flat_bitmat = gf8.expand_bitmatrix(self._flat_coding_matrix())

            def impl(data, bitmat):
                import jax.numpy as jnp

                data = jnp.asarray(data, dtype=jnp.uint8)
                b, k, s = data.shape
                cols = data.transpose(1, 0, 2).reshape(k, b * s)
                out = gf8.bitmatrix_matmul(bitmat, cols)
                return out.reshape(out.shape[0], b, s).transpose(1, 0, 2)

            self._enc_jit = (jax.jit(impl), flat_bitmat)
        fn, bitmat = self._enc_jit
        return fn(data, bitmat)

    def decode_batch(self, erasures, chunks, want=None):
        """Batched single-pattern reconstruction, walking layers bottom-up
        exactly like decode_chunks.  ``chunks``: (B, n, S) in logical order
        with zeros at erased ids; ``erasures`` = every unavailable logical
        id; ``want`` = subset to return (default all).  Returns
        (B, len(want), S).  Jitted per erasure pattern: the whole walk is
        one device dispatch, recovery plans cached like the reference's
        decode-table caches."""
        import jax

        if want is None:
            want = tuple(erasures)
        key = (tuple(erasures), tuple(want))
        cached = self._dec_jit.get(key)
        if cached is None:
            cached = self._dec_jit[key] = self._build_flat_decode(key)
        fn, bitmat, src_ids = cached
        return fn(bitmat, jax.numpy.asarray(chunks), src_ids)

    def _build_flat_decode(self, key):
        """Compose the bottom-up layer walk for one erasure pattern into
        ONE recovery matrix over the AVAILABLE logical chunks (round 5;
        same flattening as encode — the walk is linear, so the per-step
        tiny-K matmuls + scatters collapse to a single gather+matmul).
        Host-side per pattern, cached like the reference decode tables."""
        import numpy as np

        from ceph_tpu.ec.codec import _gather_encode_batch_jit
        from ceph_tpu.ops import gf8

        erasures, want = key
        steps, out_pos = self._decode_plan(erasures, want)
        logical_to_pos = list(self.chunk_mapping)
        avail_logical = tuple(e for e in range(self.chunk_count)
                              if e not in erasures)
        basis = {logical_to_pos[e]: i
                 for i, e in enumerate(avail_logical)}
        expr: dict = {}
        for p, i in basis.items():
            row = np.zeros(len(avail_logical), dtype=np.uint8)
            row[i] = 1
            expr[p] = row
        for layer, local_erasures, layer_erased in steps:
            src = self._layer_src(layer, local_erasures)
            rmat = layer.erasure_code.engine.decode_matrix(
                src, local_erasures)              # (out, src) bytes
            for r, out_local in enumerate(local_erasures):
                acc = np.zeros(len(avail_logical), dtype=np.uint8)
                for j, s_local in enumerate(src):
                    coef = int(rmat[r, j])
                    if coef:
                        acc ^= gf8.gf_mul(coef,
                                          expr[layer.chunks[s_local]])
                expr[layer.chunks[out_local]] = acc
        flat = np.stack([expr[p] for p in out_pos])
        # round 6 (locality): drop all-zero columns so the device gather
        # reads ONLY the chunks the composed recovery actually uses — a
        # single local erasure pulls its l+1-group, not all n-1 survivors
        # (the reference's minimum_to_decode read set, ErasureCodeLrc.cc:572,
        # applied to the batched matmul).  Coefficients are untouched, so
        # the result stays bit-identical; only the source set shrinks.
        used = np.flatnonzero(flat.any(axis=0))
        if used.size == 0:
            used = np.arange(min(1, len(avail_logical)))
        flat = np.ascontiguousarray(flat[:, used])
        src_ids = tuple(avail_logical[int(i)] for i in used)
        bitmat = gf8.expand_bitmatrix(flat)
        return _gather_encode_batch_jit, bitmat, src_ids

    # -- bit-planar device layout (round 6) ---------------------------------
    #
    # LRC's layer walk is flattened to single matrices (encode: the
    # composed generator; decode: the composed pruned recovery), so the
    # planar path is the same one-matmul story as the plain matrix codes:
    # packed planes in, packed planes out, conversion only at the host
    # boundary.  LRC layers are w=8 matrix codes, so w is always 8 here.

    def planar_supported(self, chunk_size: int) -> bool:
        from ceph_tpu.ec.planar import PlanarBatch

        return PlanarBatch.supported(chunk_size, 8)

    def to_planar(self, batch):
        from ceph_tpu.ec.planar import PlanarBatch

        return PlanarBatch.from_batch(batch, w=8)

    def encode_planar(self, pb):
        from ceph_tpu.ops import gf8

        if self._enc_planar_bitmat is None:
            self._enc_planar_bitmat = gf8.expand_bitmatrix(
                self._flat_coding_matrix())
        planes = gf8.planar_matmul(self._enc_planar_bitmat, pb.planes)
        return pb.with_planes(planes, self.chunk_count -
                              self.data_chunk_count)

    def decode_planar(self, erasures, pb, want=None):
        from ceph_tpu.ec.planar import _select_chunk_rows
        from ceph_tpu.ops import gf8

        if want is None:
            want = tuple(erasures)
        key = (tuple(erasures), tuple(want))
        cached = self._dec_jit.get(key)
        if cached is None:
            cached = self._dec_jit[key] = self._build_flat_decode(key)
        _, bitmat, src_ids = cached
        src_planes = _select_chunk_rows(pb.planes, 8, src_ids)
        return pb.with_planes(gf8.planar_matmul(bitmat, src_planes),
                              len(want))

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
