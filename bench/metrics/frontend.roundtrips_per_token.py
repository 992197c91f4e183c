"""Host-to-device dispatches of the scheduler (prefill, insert, decode chunk,
retire: ``SchedStats.roundtrips``) per generated token."""


def read(rec):
    return rec["stats"]["roundtrips"] / rec["stats"]["tokens"]
