#!/bin/sh
# Build the native runtime pieces into native/lib/ (or the directory
# given as $1 — serve/native_build.py builds into a scratch directory and
# renames into place). -march=native: the result is for THIS host only.
set -e
cd "$(dirname "$0")"
OUT=${1:-lib}
mkdir -p "$OUT"
g++ -O3 -march=native -std=c++17 -shared -fPIC -o "$OUT/libfeature_store.so" feature_store.cpp
echo "built $OUT/libfeature_store.so"
g++ -O3 -march=native -std=c++17 -shared -fPIC -o "$OUT/libwire_codec.so" wire_codec.cpp
echo "built $OUT/libwire_codec.so"
