"""Cut a recorded ``.xplane.pb`` down to a text proto small enough to
commit: the device's ``XLA Ops`` line for the first ``seconds`` and the
benchmark's own host spans. Used once, on the chip, to make
``recorded_trace.txt``; needs tensorflow's xplane proto, which the tests
do not (``ProfileData.from_text_proto`` reads the result).

    python3 benchmark/tests/cut_trace.py <in.xplane.pb> <out.txt> [seconds]
"""

import sys


def main(src: str, dst: str, seconds: float = 0.35) -> None:
    from google.protobuf import text_format
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    t0 = None
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        kept_plane = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            events = []
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if not device and not name.startswith("bench:"):
                    continue
                at = line.timestamp_ns * 1000 + ev.offset_ps
                if t0 is None:
                    t0 = at
                if at - t0 > seconds * 1e12:
                    continue
                events.append(ev)
            if not events:
                continue
            kept = kept_plane.lines.add(
                id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for ev in events:
                kept.events.add(metadata_id=ev.metadata_id,
                                offset_ps=ev.offset_ps,
                                duration_ps=ev.duration_ps)
                name = plane.event_metadata[ev.metadata_id].name
                if "tpu_custom_call" not in name:
                    name = name[:160]
                kept_plane.event_metadata[ev.metadata_id].id = ev.metadata_id
                kept_plane.event_metadata[ev.metadata_id].name = name
    with open(dst, "w") as f:
        f.write(text_format.MessageToString(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(float(x) for x in sys.argv[3:4]))
