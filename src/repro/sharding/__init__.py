from .ctx import ShardingCtx, shard_hint, use_sharding, current

__all__ = ["ShardingCtx", "shard_hint", "use_sharding", "current"]
