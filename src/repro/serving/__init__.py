"""Serving substrate: learned paged-KV cache + continuous batching engine
+ the mixed read/write index engine over range shards (a single index is
its one-shard case) and the incremental device mirror."""
from .kv_cache import LearnedPageTable, PagePool
from .engine import ServeEngine, Request
from .index_engine import IndexRequest, IndexShard
from .sharded_engine import ShardedIndexEngine

__all__ = ["LearnedPageTable", "PagePool", "ServeEngine", "Request",
           "IndexRequest", "IndexShard", "ShardedIndexEngine"]
