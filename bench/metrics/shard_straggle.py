"""shard_straggle: what the slowest shard costs a sharded race, over the
window's ``race.epoch`` spans: Σ over epochs of the largest shard's
``shard_coord_ops`` ÷ Σ of their mean over the shards. The host steps the
shards in lockstep, so every epoch lasts as long as its busiest shard:
1.0 when the shards share the work evenly, S when one shard does it all.
None where no epoch records a split by shard (one chip)."""


def read(run):
    most = mean = 0.0
    for e in run.events:
        per = e.get("attrs", {}).get("shard_coord_ops")
        if e.get("name") == "race.epoch" and per:
            most += max(per)
            mean += sum(per) / len(per)
    return most / mean if mean > 0 else None
