"""Engine: candidate pairs over pairs dispatched after bucket padding,
summed over every level of every mine in the window."""


def read(run):
    pairs = padded = 0
    for r in run.records:
        for level in (r.get("pair_padding") or {}).get("per_level", []):
            pairs += level["pairs"]
            padded += level["padded_to"]
    return pairs / padded if padded else None
