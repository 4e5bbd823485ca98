"""Parameter-server capability: host-resident embedding store + HET cache.

Native C++ core (``native/ps_store.cc``) re-designing the reference's
ps-lite server (ps-lite/include/ps/…) and hetu_cache client
(src/hetu_cache/…) for TPU hosts — see module docstrings for the mapping.
"""
from .build import store_kind
from .store import EmbeddingStore, default_store
from .cstable import CacheSparseTable
from .dist_store import DistCacheTable, DistributedStore
from .refcache import PerKeyCacheTable
from .ops import PSEmbeddingLookupOp, ps_embedding_lookup_op

__all__ = ["EmbeddingStore", "default_store", "store_kind", "CacheSparseTable",
           "DistCacheTable", "DistributedStore", "PerKeyCacheTable",
           "PSEmbeddingLookupOp", "ps_embedding_lookup_op"]
