#!/bin/bash
# heal_alone.py on a parent unpacked under .chip_archive/parent and on this
# checkout, in turn, under the kill mix's allocator settings.
export MALLOC_MMAP_THRESHOLD_=33554432 MALLOC_TRIM_THRESHOLD_=17179869184 MALLOC_TOP_PAD_=268435456
for root in .chip_archive/parent . . .chip_archive/parent; do
  [ -d "$root/torchft_tpu" ] || continue
  python3 scripts/heal_alone.py --root "$root" --repeats "${REPEATS:-4}" 2>/dev/null
done
