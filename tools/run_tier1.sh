#!/usr/bin/env bash
# Tier-1 verify: the fast suite as the driver runs it after every PR (the
# `commands` of the driver's /root/TESTS_LAST_RUN.json, to the letter): six
# xdist workers, cases handed out one at a time (--dist load), a 1470 s clock,
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 (six processes may each load libtpu), passes
# counted from the junit XML (DOTS_PASSED) and the workers that died counted
# from the log (WORKERS_DOWN: anything but 0 is a blocker, whatever passed).
# Run THIS script, from the repo root, so the command cannot drift.
# ROADMAP.md's "Tier-1 verify" line is kept as the record of the older
# single-process command; where the two differ, this file (and the driver)
# are right.
#
# A run cut at the clock exits 124 and counts only as far as it got: what a
# new test file may add is in ROADMAP.md, D0.
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist load --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
