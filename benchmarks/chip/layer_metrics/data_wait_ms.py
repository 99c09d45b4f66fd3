"""Mean host time per step that the train thread spent fetching the next
batch (the benchmark's ``data_wait`` span over the whole window). Near 0
while the generator keeps up; it grows when the generator runs late."""


def read(r):
    waits = r.spans["data_wait"]
    return 1e3 * sum(waits) / len(waits) if waits else None
