"""What is alive where a compiled program's temporaries peak, from XLA's buffer assignment.
usage: python peak.py <dump dir of `compile_epoch.py <cell> --dump dir`> [values to list]
Reads `*buffer-assignment.txt`: the largest preallocated-temp allocation's values (size,
offset) and the module's BufferLiveRange (logical times of the flattened schedule), and
prints the time at which the live values of a megabyte or more sum highest, with the
values alive then. The allocation is a little larger than that sum: the heap's packing,
which moves by 0.1 GB between compiles of programs with the same live set."""
import glob, re, sys

lines = open(glob.glob(sys.argv[1] + "/*buffer-assignment.txt")[0]).read().splitlines()
top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 40
allocations = [(i, int(re.search(r"size (\d+)", l).group(1))) for i, l in enumerate(lines)
               if l.startswith("allocation ") and "preallocated-temp" in l]
start, size = max(allocations, key=lambda a: a[1])
values, i = {}, start + 1
while lines[i].startswith(" value:"):
    m = re.match(r" value: <\d+ (.+?) @\d+> \(size=(\d+),offset=(\d+)\): (.*)", lines[i])
    name = m.group(1) if m.group(1).endswith("}") else m.group(1) + "{}"
    values[name] = (int(m.group(2)), int(m.group(3)), m.group(4)[:70])
    i += 1
ranges, j = {}, lines.index("  BufferLiveRange:") + 1
while lines[j].startswith("    "):
    m = re.match(r"    (.+):(\d+)-(\d+)$", lines[j])
    if m:
        ranges[m.group(1)] = (int(m.group(2)), int(m.group(3)))
    j += 1
schedule, k = [], lines.index("  InstructionSequence:") + 1
while re.match(r"    \d+:", lines[k]):
    schedule.append(lines[k].split(":", 1)[1].strip())
    k += 1
large = [n for n in values if n in ranges and values[n][0] >= 1 << 20]
alive = {}                              # time -> {offset: bytes}: values that share a buffer count once
for n in large:
    nbytes, offset, _ = values[n]
    for t in range(ranges[n][0], ranges[n][1] + 1):
        at = alive.setdefault(t, {})
        at[offset] = max(at.get(offset, 0), nbytes)
totals = {t: sum(at.values()) for t, at in alive.items()}
peak = max(totals, key=totals.get)
print("allocation", size, "largest live sum", totals[peak], "at time", peak,
      schedule[peak] if peak < len(schedule) else "?")
for nbytes, n in sorted(((values[n][0], n) for n in large
                         if ranges[n][0] <= peak <= ranges[n][1]), reverse=True)[:top_n]:
    print(f"  {nbytes / 1e6:9.1f} MB  {n:48s} {values[n][2]:70s} {ranges[n]}")
