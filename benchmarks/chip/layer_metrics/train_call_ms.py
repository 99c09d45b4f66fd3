"""Mean host time per step inside the one call that trains a batch (the
benchmark's ``train_call`` span over the whole window): input placement
and dispatch in device cells; mapper, miss import and dispatch in cached
cells."""


def read(r):
    calls = r.spans["train_call"]
    return 1e3 * sum(calls) / len(calls) if calls else None
