"""Device-sharded design-space search dispatch (``shard_eval``) and the
multi-process work queue of the guided search (``work_queue``)."""
