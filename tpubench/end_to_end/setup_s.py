"""Seconds from process start to the first measured chunk or request:
building the deployment, planning it, and warming up the cell's shapes,
compilation included."""


def read(out):
    return out.setup_s
