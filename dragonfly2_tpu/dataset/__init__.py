"""Dataset plane: webdataset tar shards as a JAX-ready streaming input.

Layers (each its own module, importable without jax until device landing
is actually requested):

  tar_index     one-pass tar header walk → compact per-shard sample
                index, cached pod-wide as a P2P object
  shard_reader  sample byte spans → ranged P2P tasks (embedded daemon or
                object-gateway transport), pooled span buffers
  loader        deterministic pod-sharded epoch iterator with bounded
                readahead (exactly-once per epoch across hosts)
  device_feed   fixed-size record batches landed via ops.hbm_sink with
                on-device verification; NumPy fallback on CPU backends
"""

from dragonfly2_tpu.dataset.tar_index import (   # noqa: F401
    Sample,
    ShardIndex,
    TarIndexer,
    TarIndexError,
    TarMember,
    TruncatedShardError,
    build_index_from_task,
    fetch_or_build_index,
    index_tar_bytes,
)
from dragonfly2_tpu.dataset.shard_reader import (   # noqa: F401
    DaemonRangeFetcher,
    GatewayRangeFetcher,
    ShardReadError,
    ShardReader,
)
from dragonfly2_tpu.dataset.loader import (   # noqa: F401
    LoaderError,
    LoaderOptions,
    PodShardedLoader,
    epoch_order,
    host_partition,
    interleave_shards,
    plan_host_epoch,
)


def __getattr__(name):
    # device_feed pulls in ops/hbm_sink (jax) lazily.
    if name in ("DeviceFeed", "DeviceBatch", "DeviceFeedError"):
        from dragonfly2_tpu.dataset import device_feed

        return getattr(device_feed, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
