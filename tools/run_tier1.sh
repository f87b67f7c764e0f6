#!/usr/bin/env bash
# Tier-1 verify — the fast suite as the driver runs it after every PR: six
# xdist workers, one test file per worker at a time (--dist loadfile), a
# 1470 s clock, passes counted from the junit XML. Run THIS script, from the
# repo root, so the command cannot drift. ROADMAP.md's "Tier-1 verify" line is
# kept as the record of the older single-process command; where the two
# differ, this file (and the driver) are right.
#
# A test that aborts the process (an XLA check failure) kills its worker, and
# the loadfile scheduler then never finishes: the run is cut at the clock with
# exit code 124. Such a test is a blocker, not a slow test.
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; exit $rc
