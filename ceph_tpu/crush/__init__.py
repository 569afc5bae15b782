"""CRUSH placement: map structures, straw2, scalar oracle, vmapped mapper.

Behavioral mirror of reference src/crush/ (mapper.c, hash.c, builder.c,
crush.h): deterministic hierarchical placement with straw2 buckets,
firstn/indep selection, tunable retry semantics — rebuilt so a whole
OSDMap's PG->OSD mapping evaluates as one batched TPU dispatch.
"""

from ceph_tpu.crush.types import (  # noqa: F401
    Bucket,
    CrushMap,
    Rule,
    Tunables,
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
)
from ceph_tpu.crush.scalar import ScalarMapper  # noqa: F401
