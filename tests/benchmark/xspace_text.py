"""Builds an XSpace in text-proto form from a plain description, so that
the trace reduction can be tested on traces whose answers are known."""


def xspace(planes):
    """``planes``: {plane name: {line name: [(event name, start_us,
    duration_us), ...]}} -> text proto for ``ProfileData.from_text_proto``."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), start=1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i for i, n in enumerate(names, start=1)}
        out.append(f'planes {{ id: {pid} name: "{pname}"')
        for lid, (lname, evs) in enumerate(lines.items(), start=1):
            out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
            for name, start_us, dur_us in evs:
                out.append(f"    events {{ metadata_id: {ids[name]} "
                           f"offset_ps: {int(start_us * 1e6)} "
                           f"duration_ps: {int(dur_us * 1e6)} }}")
            out.append("  }")
        for name, i in ids.items():
            quoted = name.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{quoted}" }} }}')
        out.append("}")
    return "\n".join(out)
