#!/bin/sh
# Build the native codec extension in place. Run from the repo root.
set -e
PY_INC=$(python -c "import sysconfig; print(sysconfig.get_paths()['include'])")
EXT=$(python -c "import sysconfig; print(sysconfig.get_config_var('EXT_SUFFIX'))")
g++ -O2 -fPIC -shared -std=c++17 -pthread \
    -I"$PY_INC" \
    channeld_tpu/native/codec.cc \
    -l:libsnappy.so.1 -L/usr/lib/x86_64-linux-gnu \
    -o "channeld_tpu/native/_codec$EXT"
echo "built: channeld_tpu/native/_codec$EXT"
g++ -O2 -std=c++17 channeld_tpu/native/kcp_peer.cc \
    -o channeld_tpu/native/kcp_peer
echo "built: channeld_tpu/native/kcp_peer"
