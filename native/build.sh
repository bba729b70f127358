#!/bin/sh
# Build the host-runtime shared library next to this script.
set -e
cd "$(dirname "$0")"
# Portable flags only: the .so may be loaded on another host's CPU.
# utils/native.py rebuilds whenever this file or the source changes.
g++ -O3 -shared -fPIC -o libgrid_redistribute_native.so \
    grid_redistribute_native.cpp
echo "built native/libgrid_redistribute_native.so"
