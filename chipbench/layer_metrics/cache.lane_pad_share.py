"""Share of a KV page's bytes that is padding (the worker's gauge
``dynamo_kv_lane_pad_share`` at the window's end): heads stored as whole
128-lane rows so that the ragged kernel takes them — 50% where 64-wide heads
are padded to a row, 0 where heads are stored as they are (or two packed a
row). What the kernel's path costs the pool: the tokens a deployment can
hold are ``1 − share`` of what the heads alone would allow. A tree without
the gauge has nothing to read."""
SOURCE = "worker_metrics"


def compute(src):
    from fleet import metric_samples

    got = metric_samples(src.worker_metrics[1], "dynamo_kv_lane_pad_share")
    return 100.0 * max(got.values()) if got else None
