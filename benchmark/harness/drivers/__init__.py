"""One driver function per kind of traffic, found by the kind's name."""
